"""Crash-tolerant metrics.jsonl reading.

The learner appends one JSON record per epoch with a flush+fsync per
record (runtime/learner.py:_write_metrics), so a SIGKILL / power cut mid-
append leaves at most ONE half-written line — and only at the tail.  Every
reader of metrics.jsonl (the plot scripts via scripts/_logparse.py, the
soak/ablation tools) goes through ``read_metrics`` so that one truncated
final line is tolerated instead of breaking downstream parsing, while a
malformed line anywhere ELSE still raises: mid-file corruption is a real
integrity problem, not an artifact of the append protocol.
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List

# The metrics.jsonl KEY REGISTRY — the tolerance contract between the
# writers (Learner.update -> _write_metrics, Trainer.stats) and every
# reader (scripts/_logparse.py + the plot scripts, tools/ablate_*).
# graftlint rule MET006 statically checks both sides against this set:
# a writer emitting an unregistered key, or a consumer reading one, is a
# lint finding — so "will every reader tolerate this record" is reviewed
# HERE, once, instead of per call site.  Readers must treat every key as
# optional (records predate keys; null values are legal — win_rate /
# generation_mean are explicitly null on empty epochs).
METRIC_KEYS = frozenset({
    # identity / cadence
    "epoch", "steps", "episodes", "episodes_per_sec", "updates_per_sec",
    # evaluation / generation books
    "win_rate", "eval_games", "generation_mean", "generation_std",
    # trainer loop
    "loss", "train_steps_per_sec", "input_wait_frac", "input_wait_warmup_s",
    "mfu", "device_mean_episode_len",
    # device-replay runs, cumulative: game steps the rings have booked and
    # the rollout dispatches (ingests) that booked them, from
    # DeviceReplay.counters (host ints, one deferred ingest behind)
    "device_game_steps", "device_rollout_dispatches",
    # live pipeline / plane topology
    "pipeline", "plane",
    # serving plane (handyrl_tpu/serving): the learner writes only
    # serve_snapshot_substituted (LocalModelServer fallback count); the
    # rest are the ServingServer's periodic health records — exact keys,
    # not a prefix family, so every new serving stat is reviewed here
    "serve_snapshot_substituted", "serve_requests", "serve_replies",
    "serve_shed", "serve_deadline_miss", "serve_batches", "serve_depth",
    "serve_qps", "serve_p50_ms", "serve_p99_ms", "serve_hot_swaps",
    "serve_models", "serve_connections", "serve_errors",
    # server-resident session cache (handyrl_tpu/fleet/sessions.py),
    # folded into the ServingServer's periodic record: residency gauges
    # plus cumulative lifecycle/eviction/restore/affinity-miss counters —
    # exact keys, like serve_*, so every new session stat is reviewed here
    "session_resident", "session_spilled", "session_opened",
    "session_closed", "session_evictions", "session_restored",
    "session_affinity_miss", "session_spill_drops",
    # migration counters (docs/serving.md §Elastic fleet): sessions this
    # cache handed to / adopted from another replica on a planned retire
    # or preemption drain — the zero-loss path's own books
    "session_migrated_in", "session_migrated_out",
    # fleet front-end (handyrl_tpu/fleet/router_tier.py): the session-
    # affinity router's periodic health records — proxy volume, replica
    # liveness (fleet_replica_lost counts loss EVENTS; fleet_replicas_live
    # is the current gauge, fleet_replicas_warming the connected-but-not-
    # admitted subset), sessions routed, and orchestrated fleet-wide
    # hot-swaps
    "fleet_requests", "fleet_replies", "fleet_errors", "fleet_qps",
    "fleet_replicas", "fleet_replicas_live", "fleet_replicas_warming",
    "fleet_replica_lost", "fleet_sessions", "fleet_hot_swaps",
    # elastic fleet: autoscale actions, zero-loss migrations (events /
    # sessions moved / last handoff wall ms), bounded stateless failover
    # retries, and preemption drains handled
    "fleet_scale_ups", "fleet_scale_downs", "fleet_migrations",
    "fleet_sessions_migrated", "fleet_migration_ms",
    "fleet_failover_retries", "fleet_preempt_drains",
    # transient-fault retries the stats poll absorbed before anything was
    # declared lost (utils/retry.py) — a rising count with zero
    # fleet_replica_lost is the retry plane doing its job
    "fleet_poll_retries",
    # data flywheel, serving side (handyrl_tpu/flywheel/harvest.py folded
    # into the ServingServer's periodic record): per-session episode
    # assembly volume and the LOUD drop counters (malformed = protocol
    # breakage, truncated = abandoned/TTL'd/shed games), plus the pull
    # drain the learner ingest loop drives
    "flywheel_episodes", "flywheel_open", "flywheel_queued",
    "flywheel_dropped_malformed", "flywheel_dropped_truncated",
    "flywheel_pulled",
    # data flywheel, quality plane (handyrl_tpu/flywheel/quality.py):
    # gated promotions / gate refusals / sentinel demotions (cumulative),
    # live games booked, and the current candidate/incumbent epoch gauges
    # (null when none is staged / retained)
    "quality_promotions", "quality_gate_failures", "quality_demotions",
    "quality_games", "quality_candidate", "quality_incumbent",
    # data flywheel, learner side (handyrl_tpu/flywheel/ingest.py folded
    # into the per-epoch record): episodes fed into the EpisodeStore,
    # staleness/malformed drops at ingest, and quality-signal rollbacks
    # applied by the trainer
    "flywheel_ingested", "flywheel_ingest_stale",
    "flywheel_ingest_malformed", "flywheel_rollbacks",
    # league plane (handyrl_tpu/league): per-epoch population health from
    # LeagueLearner._epoch_hook — exact keys, like serve_*, so every new
    # league stat is reviewed here.  league_matches/forfeits/promotions
    # are cumulative; league_candidate_wp and league_elo_spread are null
    # until the respective books have games
    "league_population", "league_pool", "league_matches", "league_forfeits",
    "league_payoff_coverage", "league_candidate_wp", "league_elo_spread",
    "league_promotions",
    # low-precision fast path (models/quantize.py, docs/performance.md
    # §Low-precision): the serving plane's periodic record pins the
    # engine weight dtype and the publish-time MEASURED calibration
    # deviation — exact keys, like serve_*, so every new lowprec stat is
    # reviewed here
    "lowprec_weight_dtype", "lowprec_calib_batches",
    "lowprec_calib_max_dev", "lowprec_calib_mean_dev",
    # multi-process learner plane (parallel/distributed.py + health.py):
    # dist_processes is the run's process count; the rest are cumulative
    # cross-host health events — heartbeat misses observed, collective-
    # timeout watchdog aborts, and peer/coordinator-loss drains.  Written
    # by the coordinator's per-epoch record and, on a host fault, by the
    # final pre-exit drain record (runtime/learner.py)
    "dist_processes", "dist_heartbeat_misses", "dist_collective_timeouts",
    "dist_peer_loss_drains",
    # pod-slice actor tier (runtime/plane.py PlaneGateway): live producer
    # count at the epoch boundary plus cumulative disconnect-after-hello
    # losses (each one a degrade the surviving hosts absorbed — never a
    # wedge, by the fault matrix's asymmetry)
    "dist_actor_hosts", "dist_actor_host_losses",
    # observability plane (docs/observability.md): every record carries
    # both clocks from the single _write_metrics seam — ts (wall, absolute
    # cross-host alignment) and t_mono (monotonic, NTP-step-immune rate
    # math); readers prefer them over the record index for time axes
    "ts", "t_mono",
})
# key families written from the *_KEYS tuples (trainer/learner) and the
# per-epoch plane-health diffs; one prefix registers the family.
# rank_*: the coordinator's fold of per-rank metric snapshots relayed
# over health-plane heartbeats (HostHealthPlane.rank_aggregates — min/
# max/mean of epoch, steps, step rate, input_wait_frac, plus report
# staleness); trace_*: cumulative tracer health (spans recorded, ring
# drops) from utils/trace.trace_stats; quality_wp*: the flywheel quality
# ledger's per-snapshot live win-point family (quality_wp{epoch} — one
# gauge per epoch with reported games, from QualityLedger.snapshot)
METRIC_KEY_PREFIXES = (
    "pipe_", "plane_", "sentinel_", "rank_", "trace_", "quality_wp",
)


def append_metrics_record(path: str, record: Dict[str, Any]) -> None:
    """One flushed+fsynced appended line — the Learner._write_metrics
    discipline shared by every periodic metrics writer (serving server,
    fleet router): a kill mid-append leaves at most ONE truncated line,
    and only at the tail, which ``read_metrics`` tolerates.  Stamps the
    dual-clock seam (ts wall / t_mono monotonic) like the learner's
    records so readers align cross-host and rate-math safely."""
    import os
    import time

    record.setdefault("ts", round(time.time(), 6))
    record.setdefault("t_mono", round(time.monotonic(), 6))
    line = json.dumps(record, default=float) + "\n"
    with open(path, "a") as f:
        f.write(line)
        f.flush()
        try:
            os.fsync(f.fileno())
        except OSError:
            pass


def read_metrics(path: str, strict: bool = False) -> List[Dict[str, Any]]:
    """Parse a metrics.jsonl into a list of records.

    A truncated FINAL line (the one write a kill can interrupt) is skipped
    with a stderr note unless ``strict``; invalid JSON on any earlier line
    raises ``ValueError`` regardless.
    """
    with open(path) as f:
        lines = f.readlines()
    records: List[Dict[str, Any]] = []
    last = len(lines) - 1
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            if i == last and not strict:
                print(
                    f"[handyrl_tpu] {path}: dropping truncated final line "
                    "(half-written record from a killed run)",
                    file=sys.stderr,
                )
                break
            raise
    return records

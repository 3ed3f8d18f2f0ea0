"""Serving-plane network front: framed-socket request/reply over the
actor-plane transport.

Reuses the fault-tolerant pieces of ``runtime/connection.py`` unchanged:
length-prefixed codec frames, per-peer bounded send queues with sender
threads (one stalled client can never wedge replies to the rest), and
optional silent-peer reaping.  On top of that, one dispatch thread pulls
request frames off the hub and hands them to the router — inference
itself is asynchronous (the reply is sent from a future callback on the
owning engine's dispatcher thread), so a slow batch never blocks frame
intake, which is what lets thousands of connections share one server.

Wire protocol (codec frames, all request/reply pairs carry ``rid``):

    -> ("infer", {"rid", "model", "obs", "hidden"?, "slo_ms"?, "sid"?})
    <- ("result", {"rid", "model": served_id, "out": numpy tree, "sid"?})
    <- ("error",  {"rid", "kind": shed|deadline|stopped|bad_request|..., "msg"})
    -> ("stats", {"rid"})               <- ("stats", {"rid", "stats": {...}})
    -> ("swap",  {"rid", "id", "params"?})  <- ("swapped", {"rid", "id", "warm_ms"})
    -> ("open_session",  {"rid", "model"?})  <- ("session", {"rid", "sid"})
    -> ("close_session", {"rid", "sid"})     <- ("session_closed", {"rid", "sid", "existed"})
    -> ("export_sessions", {"rid"})     <- ("sessions_export", {"rid", "sessions", "fresh", "count"})
    -> ("import_sessions", {"rid", "sessions", "fresh"?})
                                        <- ("sessions_imported", {"rid", "count"})
    -> ("harvest_open",  {"rid", "players", "sids"})
                                        <- ("harvest_opened", {"rid", "hid"})
    -> ("harvest_step",  {"rid", "hid", "actions", "legal", "rewards", "turn"})
                                        <- ("harvest_stepped", {"rid", "hid", "steps"})
    -> ("harvest_close", {"rid", "hid", "outcome"})
                                        <- ("harvest_closed", {"rid", "hid", "kept"})
    -> ("harvest_pull",  {"rid", "max"})
                                        <- ("harvest", {"rid", "episodes", "counts"})
    -> ("report_outcome", {"rid", "model", "outcome"})
                                        <- ("outcome_recorded", {"rid"})
    -> ("heartbeat", None)              (liveness only, never replied)
    <- ("draining", {"deadline_s"})     (rid-less notice, pushed to every peer)

``export_sessions``/``import_sessions`` are the migration frames
(docs/serving.md §Elastic fleet): a planned retire drains the source
replica, pulls its whole session cache (both tiers, realized to numpy —
codec-safe), and lands it in the successor's spill ring, where the next
infer restores it bit-identically through the ``session_restored`` path.
A SIGTERM'd replica pushes the ``draining`` notice so the fleet router
runs that same handoff inside ``drain_deadline_seconds`` before the
process exits 75 (EX_TEMPFAIL — the training plane's preemption code).

An ``infer`` carrying a ``sid`` reads/writes the session's recurrent
hidden state server-side (fleet/sessions.py) — the wire carries neither
direction of it, and the reply's ``out`` has its ``hidden`` stripped.

``swap`` with no params loads ``{id}.ckpt`` digest-verified from the
checkpoint manifest; the warm-then-flip sequence lives in the router.
A ``watch_interval`` > 0 arms a manifest watcher that hot-swaps
automatically when training publishes a newer verified snapshot.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from typing import Any, Dict, List, Optional

from ..models import init_variables
from ..runtime.checkpoint import latest_verified_epoch, load_verified_params
from ..runtime.connection import (
    FramedConnection,
    QueueCommunicator,
    open_socket_connection,
    accept_socket_connections,
)
from ..fleet.sessions import SessionCache
from ..runtime.inference_engine import EngineStopped
from ..utils.metrics import append_metrics_record
from ..utils.trace import trace_event
from .router import ColdRoute, ModelRouter

__all__ = ["ServingServer", "build_serving", "serve_main"]


class ServingServer(QueueCommunicator):
    """Continuous-batching inference server over the framed transport."""

    def __init__(
        self,
        router: ModelRouter,
        serving_cfg: Dict[str, Any],
        metrics_path: Optional[str] = None,
        flywheel=None,
    ):
        cfg = dict(serving_cfg or {})
        recv_timeout = float(cfg.get("recv_timeout", 0.0)) or None
        # reply bursts ARE the product here: a pipelining client draining a
        # whole batch's replies momentarily outruns its socket, and the
        # hub's default 64-deep send queue would reap it as wedged.  Size
        # the fault boundary to the engine queue bound instead — a peer
        # that stops reading for THAT long really is gone
        super().__init__(
            recv_timeout=recv_timeout,
            send_queue_size=max(256, int(cfg.get("queue_bound", 1024))),
        )
        self.router = router
        # data flywheel (flywheel/__init__.py): harvest capture at the
        # infer/reply seams, harvest_* wire frames, and the promotion
        # gate replacing the bare manifest refresh in the watch loop.
        # None = every flywheel seam compiles out to the old behavior.
        self.flywheel = flywheel
        self.port = int(cfg.get("port", 9997))
        self.bound_port: Optional[int] = None
        self.watch_interval = float(cfg.get("watch_interval", 0.0))
        if flywheel is not None and self.watch_interval <= 0:
            # the gate/sentinel live in the watch loop — a flywheel server
            # without a watcher would stage candidates never and judge
            # nothing, so default the beat on rather than silently stall
            self.watch_interval = 1.0
        self.stats_interval = float(cfg.get("stats_interval", 30.0))
        self._default_slo_s = float(cfg.get("slo_ms", 200.0)) / 1000.0
        self._sheds = cfg.get("shed_policy", "deadline") != "none"
        self._metrics_path = metrics_path
        self._sock = None
        self._threads: List[threading.Thread] = []
        # cold resolves (disk load + warm compiles, or waiting on another
        # loader) run here: bounded workers, so a burst of requests for a
        # non-resident model queues instead of spawning a thread apiece
        from concurrent.futures import ThreadPoolExecutor

        self._cold_pool = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="serve-cold"
        )
        # server-resident recurrent sessions (docs/serving.md §Fleet tier):
        # open_session/infer(sid)/close_session pin hidden state here so
        # the wire carries only observations.  session_capacity: 0 turns
        # the tier off — ship-state-both-ways stays the stateless fallback
        # either way.  The cache adopts the serving engine's device on
        # first use (engine placement is the router's call)
        session_capacity = int(cfg.get("session_capacity", 1024))
        self.sessions: Optional[SessionCache] = (
            SessionCache(session_capacity, int(cfg.get("session_spill", 4096)))
            if session_capacity > 0
            else None
        )
        self._stats_lock = threading.Lock()
        self.requests_in = 0
        self.replies = 0
        self.errors: Dict[str, int] = {}
        self._stats_t0 = time.monotonic()
        self._stats_served0 = 0
        # preemption drain plumbing: set by begin_drain (SIGTERM path),
        # released by the router pulling the session cache via
        # export_sessions — or by the deadline, whichever comes first
        self._sessions_exported = threading.Event()
        # HANDYRL_FAULT_SIGTERM_REPLICA="N": self-SIGTERM after N served
        # replies (runtime/faults.py — parsed here so a spawned replica
        # inherits the injection through its environment)
        from ..runtime import faults

        self._fault_sigterm_after = faults.sigterm_replica()

    # -- lifecycle ----------------------------------------------------------

    def run(self) -> "ServingServer":
        # bind AND listen synchronously: port 0 (tests) resolves
        # before return, and a client connecting the instant run() returns
        # must never see a refused connect because the accept thread
        # hasn't reached its own listen() yet
        self._sock = open_socket_connection(self.port)
        self._sock.listen(1024)
        self.bound_port = self._sock.getsockname()[1]
        targets = [self._accept_loop, self._dispatch]
        if self.watch_interval > 0:
            targets.append(self._watch_loop)
        if self._metrics_path and self.stats_interval > 0:
            targets.append(self._metrics_loop)
        for target in targets:
            t = threading.Thread(target=target, daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def shutdown(self) -> None:
        super().shutdown()
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
        self._cold_pool.shutdown(wait=False)
        self.router.stop()

    def _accept_loop(self) -> None:
        for conn in accept_socket_connections(timeout=0.5, sock=self._sock):
            if conn is None:
                if self.shutdown_flag:
                    break
                continue
            self.add_connection(conn)

    # -- request dispatch ----------------------------------------------------

    def _dispatch(self) -> None:
        while not self.shutdown_flag:
            try:
                conn, frame = self.recv(timeout=0.3)
            except _queue.Empty:
                continue
            try:
                req, data = frame
            except (TypeError, ValueError):
                continue  # malformed frame; the codec already vetted types
            if req == "heartbeat" or req == "__hb__":
                continue
            if not isinstance(data, dict):
                data = {}
            rid = data.get("rid")
            try:
                if req == "infer":
                    self._handle_infer(conn, data)
                elif req == "stats":
                    # stats_record copies + sorts every engine's latency
                    # reservoir — O(n log n) a polling dashboard must not
                    # inject into frame intake; the cold pool is idle
                    # whenever no snapshot is loading
                    self._cold_pool.submit(self._handle_stats, conn, rid)
                elif req == "swap":
                    # warm-up compiles take seconds: never on this thread —
                    # and through the BOUNDED pool, so a client looping swap
                    # frames queues instead of spawning a warming thread
                    # (and a racing publish) apiece
                    self._cold_pool.submit(self._handle_swap, conn, data)
                elif req == "open_session":
                    self._handle_open_session(conn, rid)
                elif req == "close_session":
                    self._handle_close_session(conn, rid, data.get("sid"))
                elif req == "export_sessions":
                    # realizes every resident hidden to host numpy — a
                    # device sync by design, so off the dispatch thread
                    self._cold_pool.submit(self._handle_export_sessions,
                                           conn, rid)
                elif req == "import_sessions":
                    self._cold_pool.submit(self._handle_import_sessions,
                                           conn, rid, data)
                elif req in ("harvest_open", "harvest_step",
                             "harvest_close", "harvest_pull",
                             "report_outcome"):
                    if self.flywheel is None:
                        self._error(conn, rid, "bad_request",
                                    "flywheel disabled (flywheel.enabled: false)")
                    elif req in ("harvest_close", "harvest_pull"):
                        # close finalizes + zlib-compresses a whole
                        # trajectory; pull serializes a batch of blobs —
                        # both off the dispatch thread
                        self._cold_pool.submit(self._handle_harvest,
                                               conn, rid, req, data)
                    else:
                        self._handle_harvest(conn, rid, req, data)
                else:
                    self._error(conn, rid, "bad_request",
                                f"unknown request {req!r}")
            except Exception as exc:
                # this is THE dispatch thread: no frame — however malformed
                # or unlucky — may kill it, or every client hangs forever
                # while the accept loop keeps admitting new ones
                self._error(conn, rid, "error",
                            f"{type(exc).__name__}: {exc}")

    def _handle_infer(self, conn: FramedConnection, data: Dict[str, Any]) -> None:
        with self._stats_lock:
            self.requests_in += 1
        # the SLO clock starts at frame arrival: a cold-routed request that
        # waits behind a snapshot load must not have its budget re-based
        # when the pool task finally runs it.  Assigned UNCONDITIONALLY —
        # a wire-supplied "_arrival" would let a client mint itself an
        # unshedable (or instantly-expired) deadline
        data["_arrival"] = time.monotonic()
        try:
            # hot path: resident routes resolve + submit inline.  ColdRoute
            # (disk load + warm compiles ahead) re-dispatches to the bounded
            # cold pool — the resolve call ITSELF makes the decision, so no
            # check-then-resolve race can sneak cold work onto this thread
            self._do_infer(conn, data, allow_cold=False)
        except ColdRoute:
            self._cold_pool.submit(self._infer_cold, conn, data)

    def _handle_stats(self, conn: FramedConnection, rid) -> None:
        try:
            self.send(conn, ("stats", {"rid": rid, "stats": self.stats_record()}))
        except Exception as exc:  # a pool task must never die silently
            self._error(conn, rid, "error", f"{type(exc).__name__}: {exc}")

    def _infer_cold(self, conn: FramedConnection, data: Dict[str, Any]) -> None:
        try:
            self._do_infer(conn, data)
        except Exception as exc:  # a pool task must never die silently
            self._error(conn, data.get("rid"), "error",
                        f"{type(exc).__name__}: {exc}")

    def _handle_open_session(self, conn: FramedConnection, rid) -> None:
        if self.sessions is None:
            self._error(conn, rid, "bad_request",
                        "session cache disabled (serving.session_capacity: 0)")
            return
        self.send(conn, ("session", {"rid": rid, "sid": self.sessions.open()}))

    def _handle_close_session(self, conn: FramedConnection, rid, sid) -> None:
        if self.sessions is None or not isinstance(sid, str):
            self._error(conn, rid, "bad_request", f"bad session id {sid!r}")
            return
        existed = self.sessions.close(sid)
        self.send(conn, ("session_closed",
                         {"rid": rid, "sid": sid, "existed": existed}))

    def _handle_export_sessions(self, conn: FramedConnection, rid) -> None:
        """Migration source side: hand the whole session cache (both
        tiers + fresh sids) to the caller and clear it — ownership
        transfer.  A session-less server exports empty rather than
        erroring: retiring a stateless replica is still a legal retire."""
        try:
            if self.sessions is None:
                exported: Dict[str, Any] = {"sessions": {}, "fresh": []}
            else:
                exported = self.sessions.export_all()
            self.send(conn, ("sessions_export", {
                "rid": rid,
                "sessions": exported["sessions"],
                "fresh": exported["fresh"],
                "count": len(exported["sessions"]),
            }))
            # signalled only AFTER the reply frame is on the wire: a
            # draining serve_main shuts the socket down the moment this
            # event fires, and the export must not be cut mid-flight
            self._sessions_exported.set()
        except Exception as exc:  # a pool task must never die silently
            self._error(conn, rid, "error", f"{type(exc).__name__}: {exc}")

    def _handle_import_sessions(self, conn: FramedConnection, rid,
                                data: Dict[str, Any]) -> None:
        """Migration successor side: adopt the retiring replica's
        sessions into the spill tier (restored bit-identically on their
        next infer through the counted ``session_restored`` path)."""
        try:
            if self.sessions is None:
                self._error(conn, rid, "bad_request",
                            "session cache disabled "
                            "(serving.session_capacity: 0)")
                return
            n = self.sessions.adopt(
                data.get("sessions") or {}, data.get("fresh") or ()
            )
            self.send(conn, ("sessions_imported", {"rid": rid, "count": n}))
        except Exception as exc:  # a pool task must never die silently
            self._error(conn, rid, "error", f"{type(exc).__name__}: {exc}")

    def _handle_harvest(self, conn: FramedConnection, rid, req: str,
                        data: Dict[str, Any]) -> None:
        """Data-flywheel wire frames (docs/serving.md §Data flywheel).
        The client reports the half of each step only it knows (sampled
        action, legal set, rewards, turn, final outcome); the recorder
        already captured the server half at the infer/reply seams."""
        from ..flywheel import HarvestError

        recorder = self.flywheel.recorder
        try:
            if req == "harvest_open":
                hid = recorder.open_episode(
                    data.get("players") or (), data.get("sids") or ()
                )
                self.send(conn, ("harvest_opened", {"rid": rid, "hid": hid}))
            elif req == "harvest_step":
                steps = recorder.step(
                    data.get("hid"), data.get("actions") or (),
                    data.get("legal") or (), data.get("rewards") or (),
                    data.get("turn"),
                )
                self.send(conn, ("harvest_stepped",
                                 {"rid": rid, "hid": data.get("hid"),
                                  "steps": steps}))
            elif req == "harvest_close":
                episode = recorder.close(data.get("hid"), data.get("outcome"))
                self.send(conn, ("harvest_closed",
                                 {"rid": rid, "hid": data.get("hid"),
                                  "kept": episode is not None}))
            elif req == "harvest_pull":
                episodes, counts = recorder.pull(int(data.get("max", 64)))
                self.send(conn, ("harvest", {"rid": rid, "episodes": episodes,
                                             "counts": counts}))
            else:  # report_outcome
                self.flywheel.quality.record_outcome(
                    data.get("model"), data.get("outcome")
                )
                self.send(conn, ("outcome_recorded", {"rid": rid}))
        except (HarvestError, ValueError) as exc:
            self._error(conn, rid, "bad_request", str(exc))
        except Exception as exc:  # a pool task must never die silently
            self._error(conn, rid, "error", f"{type(exc).__name__}: {exc}")

    def begin_drain(self, deadline_s: float = 60.0) -> bool:
        """Preemption handoff (SIGTERM path, docs/fault_tolerance.md):
        push a rid-less ``draining`` notice to every peer, then wait for
        a router to pull the session cache via ``export_sessions`` — or
        for the deadline.  Returns True if the handoff happened.  A
        server with no peers or no sessions returns immediately: there
        is nothing to hand off, and the drain must never outwait its
        own deadline doing nothing."""
        for conn in self.connections():
            self.send(conn, ("draining", {"deadline_s": float(deadline_s)}))
        if self.sessions is None or self.connection_count() == 0:
            return False
        stats = self.sessions.stats()
        if stats["session_resident"] + stats["session_spilled"] == 0:
            return False
        deadline = time.monotonic() + max(0.0, float(deadline_s))
        while time.monotonic() < deadline:
            if self._sessions_exported.wait(timeout=0.1):
                return True
        return self._sessions_exported.is_set()

    def _do_infer(self, conn: FramedConnection, data: Dict[str, Any],
                  allow_cold: bool = True) -> None:
        rid = data.get("rid")
        model_id = data.get("model", -1)
        if self.flywheel is not None:
            # shadow slice: a latest-addressed request may be rewritten to
            # the staged candidate (explicit/pinned ids pass untouched —
            # the reply's served id tells the client which epoch answered,
            # and harvest clients pin their whole game to that id)
            model_id = self.flywheel.shadow_model(model_id)
        # the deadline is based at frame ARRIVAL for the default budget
        # too, not just explicit slo_ms — otherwise a cold-routed request's
        # wait behind a snapshot load would never count against it (the
        # engine would stamp a fresh budget at submit time)
        arrival = data.get("_arrival", time.monotonic())
        deadline = arrival + self._default_slo_s if self._sheds else None
        slo_ms = data.get("slo_ms")
        if slo_ms is not None:
            try:
                deadline = arrival + float(slo_ms) / 1000.0
            except (TypeError, ValueError):
                self._error(conn, rid, "bad_request",
                            f"slo_ms={slo_ms!r} is not a number")
                return
        sid = data.get("sid")
        hidden = data.get("hidden")
        if sid is not None and self.sessions is None:
            self._error(conn, rid, "bad_request",
                        "session cache disabled (serving.session_capacity: 0)")
            return
        if sid is not None and hidden is None:
            # session path: the hidden state lives HERE, next to the model
            # (an explicit wire hidden still wins — the stateless override).
            # A miss (spill overflow, or a session re-routed off a dead
            # replica) falls back to the model's initial state and is
            # counted — the client keeps playing, degraded loudly in stats
            hidden, _status = self.sessions.lookup(sid)
        if self.flywheel is not None and sid is not None:
            # harvest capture, request half: the observation for this
            # session's player (no-op unless the sid is bound to an open
            # harvest episode)
            self.flywheel.capture_request(sid, data.get("obs"))
        for attempt in (0, 1):
            try:
                served, route = self.router.resolve(model_id, allow_cold=allow_cold)
            except ColdRoute:
                raise
            except Exception as exc:
                self._error(conn, rid, getattr(exc, "kind", "bad_request"), str(exc))
                return
            fut = route.submit(data.get("obs"), hidden, deadline)
            if (
                attempt == 0
                and fut.done()
                and isinstance(fut.exception(), EngineStopped)
            ):
                # raced an eviction's drain between resolve and submit:
                # re-resolve once — the request must not be dropped by a
                # retirement it never chose
                continue
            break
        if sid is not None and self.sessions.device is None:
            # adopt the engine's device once so resident state stacks into
            # future batches without a per-request host upload
            self.sessions.device = getattr(route, "device", None)
        fut.add_done_callback(
            lambda f, c=conn, r=rid, s=served, a=arrival, i=sid:
                self._reply(c, r, s, f, a, i)
        )

    def _reply(self, conn: FramedConnection, rid, served, fut,
               arrival: Optional[float] = None, sid=None) -> None:
        exc = fut.exception()
        if arrival is not None:
            # the request lifecycle as one span: frame arrival (admission)
            # -> queue -> batch dispatch -> this reply callback.  The
            # nested "serve.batch" span (batcher.py) shows how much of it
            # was device work vs queueing
            trace_event(
                "serve.request", time.monotonic() - arrival, t0=arrival,
                plane="serving", ok=exc is None,
            )
        if exc is None:
            with self._stats_lock:
                self.replies += 1
                replies = self.replies
            if self._fault_sigterm_after is not None \
                    and replies == self._fault_sigterm_after:
                # fault injection: a spot-instance preemption lands mid-
                # storm — SIGTERM to our own process; serve_main's handler
                # drives the draining broadcast -> session handoff -> 75
                import os
                import signal

                print(f"serving: FAULT sigterm_replica after {replies} "
                      "replies — raising SIGTERM")
                os.kill(os.getpid(), signal.SIGTERM)
            out = fut.result()
            if self.flywheel is not None and sid is not None:
                # harvest capture, reply half: the policy/value this epoch
                # produced — BEFORE the reply frame leaves, so a client
                # that waits for its reply can close the step knowing the
                # capture is already on the books
                self.flywheel.capture_reply(sid, served, out)
            if sid is not None and isinstance(out, dict) and "hidden" in out:
                # the session's whole point: the next-step state stays
                # here (store() re-pins it device-side) and the reply
                # frame sheds its largest tensor.  out is this request's
                # own scatter slice, so popping mutates nothing shared
                self.sessions.store(sid, out.pop("hidden"))
            reply = {"rid": rid, "model": served, "out": out}
            if sid is not None:
                reply["sid"] = sid
            self.send(conn, ("result", reply))
        else:
            kind = getattr(exc, "kind", None) or (
                "stopped" if isinstance(exc, EngineStopped) else "error"
            )
            self._error(conn, rid, kind, str(exc))

    def _error(self, conn: FramedConnection, rid, kind: str, msg: str) -> None:
        with self._stats_lock:
            self.errors[kind] = self.errors.get(kind, 0) + 1
        self.send(conn, ("error", {"rid": rid, "kind": kind, "msg": msg}))

    def _handle_swap(self, conn: FramedConnection, data: Dict[str, Any]) -> None:
        rid = (data or {}).get("rid")
        try:
            sid = int(data["id"])
            params = data.get("params")
            if params is None:
                params = load_verified_params(
                    self.router.model_dir, sid, self.router._params_template()
                )
            warm_ms = self.router.publish(sid, params)
            self.send(conn, ("swapped", {"rid": rid, "id": sid, "warm_ms": warm_ms}))
        except Exception as exc:
            self._error(conn, rid, "swap_failed", f"{type(exc).__name__}: {exc}")

    # -- checkpoint watcher --------------------------------------------------

    def _watch_loop(self) -> None:
        while not self.shutdown_flag:
            time.sleep(self.watch_interval)
            if self.shutdown_flag:
                return
            try:
                if self.flywheel is not None:
                    # the flywheel beat subsumes the bare refresh: with
                    # gating off it IS maybe_refresh, with gating on it
                    # stages/judges candidates and runs the sentinel
                    event = self.flywheel.tick()
                    if event is not None:
                        print(f"serving: flywheel: {event}")
                    continue
                published = self.router.maybe_refresh()
                if published is not None:
                    print(f"serving: hot-swapped to verified snapshot {published}")
            except Exception as exc:
                # a corrupt manifest mid-write etc. must not kill the watcher
                print(f"serving: refresh failed: {type(exc).__name__}: {exc}")

    # -- stats / metrics -----------------------------------------------------

    def stats_record(self, advance_window: bool = False) -> Dict[str, Any]:
        """One metrics.jsonl-shaped record of the serving plane's health.
        Every key here is registered in utils.metrics.METRIC_KEYS (MET006).
        qps is over the window since it was last ADVANCED — only the
        periodic metrics loop advances it, so a dashboard polling wire
        stats cannot shrink (and thereby noise up) the recorded windows."""
        rstats = self.router.stats()
        now = time.monotonic()
        with self._stats_lock:
            requests_in = self.requests_in
            # self.replies is the wire truth: it counts every successful
            # reply including instant (model 0) and ensemble routes, which
            # no single engine's requests_served sees
            replies = self.replies
            errors = dict(self.errors)
            dt = max(now - self._stats_t0, 1e-6)
            served_delta = replies - self._stats_served0
            if advance_window:
                self._stats_t0 = now
                self._stats_served0 = replies
        record: Dict[str, Any] = {
            "serve_requests": requests_in,
            "serve_replies": replies,
            "serve_shed": rstats["requests_shed"],
            "serve_deadline_miss": rstats["deadline_misses"],
            "serve_batches": rstats["batches_served"],
            "serve_depth": rstats["depth"],
            "serve_qps": round(served_delta / dt, 2),
            "serve_p50_ms": rstats["p50_ms"],
            "serve_p99_ms": rstats["p99_ms"],
            "serve_hot_swaps": rstats["hot_swaps"],
            "serve_models": rstats["models"],
            "serve_snapshot_substituted": rstats["substituted"],
            "serve_connections": self.connection_count(),
            "serve_errors": sum(errors.values()),
        }
        if self.sessions is not None:
            record.update(self.sessions.stats())
        if self.flywheel is not None:
            # flywheel_* harvest counters + quality_* gate/sentinel books
            # (quality_wp{epoch} rides the registered prefix family)
            record.update(self.flywheel.stats_record())
        if getattr(self.router, "weight_dtype", "float32") != "float32":
            # low-precision rung: dtype pin + the publish-time MEASURED
            # calibration record (None until a calibration_source is wired
            # and a publish has run) — keys registered in METRIC_KEYS
            record["lowprec_weight_dtype"] = self.router.weight_dtype
            calib = getattr(self.router, "last_calibration", None)
            if calib:
                record["lowprec_calib_batches"] = calib["calib_batches"]
                record["lowprec_calib_max_dev"] = calib["calib_max_dev"]
                record["lowprec_calib_mean_dev"] = calib["calib_mean_dev"]
        return record

    def _metrics_loop(self) -> None:
        while not self.shutdown_flag:
            time.sleep(self.stats_interval)
            if self.shutdown_flag:
                return
            try:
                self._write_metrics(self.stats_record(advance_window=True))
            except Exception as exc:
                print(f"serving: metrics write failed: {type(exc).__name__}: {exc}")

    def _write_metrics(self, record: Dict[str, Any]) -> None:
        """Learner._write_metrics discipline: one flushed+fsynced append
        per record (timestamp seam included), so readers tolerate at most
        a truncated tail line — shared with the fleet router's records."""
        append_metrics_record(self._metrics_path, record)


def build_serving(args: Dict[str, Any]) -> ServingServer:
    """The serving plane `main.py --serve` runs, built and listening:
    router, newest verified snapshot published, optional flywheel, socket
    server started.  The router is ``server.router``.

    Publishes the newest manifest-verified snapshot; an EMPTY model dir
    gets fresh-init params under id 0 (a cold dev server still answers).
    A model dir that is there but cannot be scanned raises — serving
    random weights in place of a checkpoint that failed to load is not a
    degraded mode, it is a wrong answer.
    """
    from ..envs import make_env, prepare_env
    from ..utils import trace

    train = args["train_args"]
    env_args = args["env_args"]
    if trace.configure(train.get("trace")):
        print(f"serving: trace spans -> {trace.current_path()}")
    prepare_env(env_args)
    env = make_env(env_args)
    module = env.net()
    env.reset()
    template_obs = env.observation(env.players()[0])
    model_dir = train.get("model_dir", "models")

    router = ModelRouter(
        module, template_obs, train.get("serving", {}), model_dir=model_dir
    )
    newest = latest_verified_epoch(model_dir)
    if newest > 0:
        template = init_variables(module, env)["params"]
        params = load_verified_params(model_dir, newest, template, pre_verified=True)
        router.publish(newest, params)
    else:
        # cold dev server: fresh-init weights under id 0 — the untrained/
        # random id, which also keeps the manifest watcher's newer-than-
        # current check able to pick up training's very first epoch
        router.publish(0, init_variables(module, env)["params"])

    flywheel = None
    fly_cfg = train.get("flywheel", {}) or {}
    if fly_cfg.get("enabled"):
        from ..flywheel import FlywheelPlane

        obs_spec_fn = None
        if train.get("obs_int8"):
            # harvested episodes must quantize under the SAME env spec the
            # self-play Generator uses, or ring ingest would mix scales
            from ..models.quantize import obs_quant_spec

            obs_spec_fn = lambda obs: obs_quant_spec(env, obs=obs)
        gen_args = {
            "gamma": train.get("gamma", 0.8),
            "compress_steps": train.get("compress_steps", 8),
            "observation": train.get("observation", True),
            "obs_int8": bool(train.get("obs_int8", False)),
        }
        flywheel = FlywheelPlane(
            router, model_dir, fly_cfg, gen_args, obs_spec_fn=obs_spec_fn
        )
        print(f"serving: data flywheel on (gate_promotions="
              f"{bool(fly_cfg.get('gate_promotions', True))})")

    server = ServingServer(
        router, train.get("serving", {}),
        metrics_path=train.get("metrics_path"), flywheel=flywheel,
    ).run()
    print(f"serving: listening on port {server.bound_port} "
          f"(model {router.latest_id()}, dir {model_dir!r})", flush=True)
    return server


def serve_main(args: Dict[str, Any]) -> None:
    """`main.py --serve`: standalone serving plane for the configured env
    (``build_serving``), served until interrupted.  With
    ``serving.watch_interval`` > 0 the server follows the training run's
    checkpoints: every new verified snapshot hot-swaps in with zero
    dropped requests.
    """
    server = build_serving(args)
    train = args["train_args"]

    # preemption-aware replica (docs/fault_tolerance.md): SIGTERM — the
    # spot-instance eviction signal — triggers a bounded drain: broadcast
    # the draining notice, wait for a fleet router to pull the session
    # cache (export_sessions) inside drain_deadline_seconds, then exit 75
    # (EX_TEMPFAIL) so a launcher replaces the replica.  SIGINT (an
    # operator's Ctrl-C) keeps the immediate shutdown.
    import signal
    import sys as _sys

    preempted = threading.Event()
    try:
        signal.signal(signal.SIGTERM, lambda *_: preempted.set())
    except ValueError:
        pass  # not the main thread (embedded use): no preemption handler
    try:
        while not preempted.wait(timeout=1.0):
            pass
        deadline_s = float(train.get("drain_deadline_seconds", 60.0))
        print(f"serving: SIGTERM — draining sessions "
              f"(deadline {deadline_s:.0f}s)", flush=True)
        handed_off = server.begin_drain(deadline_s)
        if handed_off:
            # the export reply frame is written but the router still has
            # to READ it — closing with unread inbound frames queued (a
            # racing stats poll) would RST the socket and cut it off
            time.sleep(0.25)
        print(f"serving: drain complete (sessions handed off: {handed_off}); "
              "exiting 75 for relaunch", flush=True)
        server.shutdown()
        _sys.exit(75)
    except KeyboardInterrupt:
        print("serving: shutting down")
        server.shutdown()

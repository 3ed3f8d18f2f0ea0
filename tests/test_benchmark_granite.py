"""benchmark/tests/test_granite_rehearsal.py, collected where tests are run.
Its rehearsals run ``run.py`` on a workload of their own
(``benchmark_out/tiny_granite_actor``), so they share no output directory
with the other files that do.

Three cases of the benchmark's own files are restated here.  Each held an
earlier PR's entries to a *position* in ``BENCHMARK.json``'s lists (the last
cell of a list, the last entries of ``per_layer``) or every cell to a list,
which the cell and the five metrics PR 44 appended end; a ``model_config`` PR
may not edit a file the benchmark has, so tests/test_benchmark_hybrid.py,
test_benchmark_ouro.py and test_benchmark_rehearsals.py drop the three and
these ask what they meant (PERF.md section 7 leaves the edit to a
``benchmark`` PR).

A fourth is restated since PR 46: ``test_the_actor_cell_rehearses_on_cpu``
holds a traced rehearsal's ``counter_buffer_slots`` to be at least a block of
128 rows a routed layer and step, and the tiny cell's 12 pairs a step over 8
experts now lie in blocks of 16 (``ops/routed_experts.py`` ``block_rows``):
the case below is the benchmark's own, line for line, but for that count,
which it holds to the program's own ``row_buffer``."""

import json
import os

import pytest

from benchmark.tests.test_granite_rehearsal import *  # noqa: F401,F403
from benchmark.tests.test_granite_rehearsal import BENCH, CELLS, REPO, _run, rehearsal
from test_setup_readers import before_pr55

# holds PR 44's entries to be the last of their lists, which PR 48's appended
# entries end: restated in tests/test_benchmark_zaya.py
del test_the_entries_are_appended_and_nothing_else_moved  # noqa: F821

ROUTED, LOOPED, ZAYA = "nemotron_twotower_train_t192", "ouro_train_t192", "zaya1_train_t192"
KANANA = "kanana2_train_t192"   # PR 52's cell: behind PR 48's wherever both are listed


@pytest.mark.parametrize("trace", [0, 1])
def test_the_actor_cell_rehearses_on_cpu(root, trace, tmp_path, monkeypatch):  # noqa: F811  (the benchmark's, restated)
    import jax.numpy as jnp

    from handyrl_tpu.ops.routed_experts import block_rows, row_buffer

    if not trace:   # the run leaves its programs as lowered: ``_run`` hands the environment on
        monkeypatch.setenv("JAX_DUMP_IR_TO", str(tmp_path))
        monkeypatch.setenv("JAX_DUMP_IR_MODES", "stablehlo")
    proc = _run(root, "tiny_granite_actor", trace)
    assert proc.returncode == 4, proc.stderr[-4000:]
    if not trace:
        # the acting step is the routed layer's forward half alone: the rows' kernel, and
        # nothing of the backward loop, whose weight sums carry an aliased operand (PR 50)
        rollouts = [path.read_text() for path in tmp_path.glob("*jit_device_rollout*")]
        assert rollouts and all("call @_rows_times" in text for text in rollouts)
        assert not any("_weight_sums" in text for text in rollouts)
    lines = proc.stdout.strip().splitlines()
    last, earlier = json.loads(lines[-1]), json.loads(lines[-2])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "compared"]
    assert last["correct"] is False and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    # the replay against the window, then the three limits of a routed net
    compared = last["compared"]
    assert {"replay_prob", "replay_value", "policy", "value", "return", "choices_agreement",
            "f32_policy", "f32_value", "f32_return"} <= set(compared)
    for name in ("choices_agreement", "f32_choices_agreement"):     # higher is better
        agreement, floor = compared.pop(name)
        assert agreement >= floor
    assert all(number <= limit for number, limit in compared.values()), compared
    checks = earlier["checks"]
    assert checks.pop("device_is_tpu") is False
    checks.pop("device_ran", None)       # a CPU trace has no device plane
    assert all(checks.values()), (checks, earlier["notes"])
    assert checks["replay_matches_window"] and checks["matches_reference"] \
        and checks["choices_agree"] and checks["matches_reference_f32"] \
        and checks["no_compile_in_window"] and checks["actor_loop_ended"]
    # whole dispatches of lanes x k, through the gateway
    counters, cell = earlier["counters"], CELLS["tiny_granite_actor"]["train_args"]
    lanes, k = cell["device_rollout_games"], cell["device_replay_k_steps"]
    assert counters["game_steps"] == counters["dispatches"] * lanes * k > 0
    assert counters["dispatches_before_window"] >= 3
    assert earlier["notes"]["param_dtypes"] == ["bfloat16"]
    assert earlier["notes"]["judged_games"]["observed_steps"] > 4
    answered = set(earlier["notes"]["metrics_answered"])
    assert answered >= set(CELLS["tiny_granite_actor"]["answers"]["traced" if trace else "untraced"])
    if trace:
        # what the step mode counted reached the run: three routed layers, every
        # row the rollout applies the net to (the acting player's of each lane),
        # top-3 of 8 with 4 held, one row buffer a layer and step
        assert 0 < counters["counter_rows_held"] <= 3 * 3 * lanes * 2 * k
        block = block_rows(lanes, 3, 8, jnp.bfloat16)
        slots = row_buffer(lanes, 3, 4, 8, block)[0] * block
        assert counters["counter_buffer_slots"] == 3 * k * slots == 3 * k * 5 * 16
        # no device plane, no program, no scope: those readers leave their metrics out
        assert not answered & {"rollout_roofline_share", "rollout_experts_share",
                               "rollout_state_share", "rollout_device_share",
                               "rollout_ms_per_dispatch", "rollout_env_share"}


def _spec():
    """``BENCHMARK.json`` without PR 55's four ``setup_*`` metrics, which stand
    last and list every cell (``before_pr55`` holds them to it): the cases
    below ask of what is left what they asked before."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return before_pr55(json.load(f))


def _lists(spec):
    return {m["name"]: m["workloads"] for g in ("end_to_end", "per_layer") for m in spec[g]
            if "workloads" in m}


def test_every_new_metric_lists_the_cell_and_has_a_reader():
    """PR 34's: the routed cell's own metrics list it first (alone, but
    where PR 48's and PR 52's cells, which run the same routed layer, joined
    it); the accepted ones it joined list it after the cells they had (and before what
    came later); the cell's entry is its file's."""
    spec = _spec()
    lists = _lists(spec)
    for name in ("ssd_roofline", "experts_roofline", "route_step_share", "ssd_step_share",
                 "expert_rows_max_over_mean"):
        assert lists[name][0] == ROUTED
        assert lists[name][1:] == ([] if name.startswith("ssd") else [ZAYA, KANANA])
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    for name in ("setup_compile_s", "train_step_device_ms", "train_mfu", "train_roofline_share",
                 "device_idle_share"):
        assert lists[name].index(ROUTED) > lists[name].index("xfmr_train_t64_dp4")
    cell = rehearsal._load(os.path.join(BENCH, "workloads"))[ROUTED]
    entry = next(w for w in spec["workloads"] if w["name"] == cell["name"])
    assert (entry["config"], entry["traffic"], entry["chips"], entry["why"]) == (
        cell["config"], cell["traffic"], cell["chips"], cell["why"])


def test_the_looped_cells_entries_stand_where_they_were_appended():
    """PR 41's: its configuration, its cell and its three metrics, one after
    another, behind everything older and before PR 44's; its name behind the
    routed cell's only in ``trained_steps_per_s``."""
    spec = _spec()
    configs = [c["name"] for c in spec["configs"]]
    cells = [w["name"] for w in spec["workloads"]]
    assert configs.index("ouro_2_6b") == 3 and cells.index(LOOPED) == 4
    names = [m["name"] for m in spec["per_layer"]]
    new = ["mlp_roofline", "attn_step_share", "norm_step_share"]
    first = names.index(new[0])
    assert names[first:first + 3] == new and first == 24
    for metric in spec["per_layer"][first:first + 3]:
        # alone, but where PR 48's and PR 52's cells, which have an ``attn`` scope too, joined it
        assert metric["workloads"] == [LOOPED] + [ZAYA, KANANA] * (
            metric["name"] == "attn_step_share")
        assert metric["moves"] == "trained_steps_per_s"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", metric["name"] + ".py"))
    lists = _lists(spec)
    listed = sorted(name for name, cells in lists.items() if LOOPED in cells)
    assert listed == sorted(new + [
        "trained_steps_per_s", "setup_compile_s", "train_step_device_ms", "train_mfu",
        "train_roofline_share", "device_idle_share"])
    for name in listed:
        before_routed = lists[name].index(LOOPED) < lists[name].index(ROUTED) \
            if ROUTED in lists[name] else False
        assert before_routed == (name not in new + ["trained_steps_per_s"])


def test_every_cell_lists_setup_compile_s_and_every_training_cell_device_idle_share():
    """A per-layer metric lists the cells that report the end-to-end metric it
    moves: ``setup_compile_s`` moves ``setup_s``, which every cell reports;
    ``device_idle_share`` moves ``trained_steps_per_s``, which the acting cell
    does not."""
    spec = _spec()
    cells = sorted(cell["name"] for cell in spec["workloads"])
    lists = _lists(spec)
    for metric in spec["per_layer"]:
        assert "workloads" in metric, metric["name"]
    assert sorted(lists["setup_compile_s"]) == cells
    assert sorted(lists["device_idle_share"]) == sorted(lists["trained_steps_per_s"])

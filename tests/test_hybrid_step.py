"""``HybridNet`` through the system's entry points: one ``TrainContext`` step
and the layout it records, the step's jaxpr beside the other nets', what the
net and its mesh refuse by name, step mode through the inference model, and
the count of the published cell's work against a hand count
(benchmark/flops/nemotron_h.py).  The net of tests/test_hybrid_net.py, from
which PR 67 cut this file.
"""

import json
import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handyrl_tpu.config import normalize_args
from handyrl_tpu.envs import make_env
from handyrl_tpu.models import HybridNet
from handyrl_tpu.parallel import TrainContext, make_mesh
from handyrl_tpu.parallel.train_step import sub_jaxprs
from nets import HYBRID, REPO, _config, _geister_windows, _load

NET = HYBRID.net
FLOPS = _load("flops", "nemotron_h.py")


@pytest.fixture(scope="module")
def geister():
    return _geister_windows(HYBRID, batch_size=3, burn_in_steps=3, forward_steps=9)


# -- the train step -----------------------------------------------------------


def test_train_step_counts_rows_and_records_its_layout(geister, tmp_path):
    from handyrl_tpu.utils import trace

    _, args, module, params, batch = geister
    trace.configure({"enabled": True, "path": str(tmp_path / "trace.jsonl")})
    try:
        ctx = TrainContext(module, args, make_mesh({"dp": 1}))
    finally:
        trace.shutdown()
    layout = [r for r in trace.read_trace(str(tmp_path / "trace.jsonl"))
              if r["name"] == "model.layout"]
    assert len(layout) == 1
    layout = [record["attrs"] for record in layout]
    assert layout[0]["pattern"] == "MEM*E" and layout[0]["experts_held"] == 4
    assert layout[0]["experts"] == 8 and layout[0]["params_mamba"] > 0
    trunk = sum(x.size for name, sub in params.items() if name.startswith("layer")
                for x in jax.tree.leaves(sub))
    assert sum(layout[0][k] for k in ("params_mamba", "params_attention", "params_experts")) == trunk

    state = ctx.init_state(params)
    state, metrics = ctx.train_step(state, ctx.put_batch(batch), 1e-4)
    metrics = jax.device_get(metrics)
    assert np.isfinite(metrics["total"]) and metrics["sentinel_bad"] == 0
    observed = float(np.sum(batch["observation_mask"]))
    # two routed layers, top-2 of 8 with 4 held: about half of the choices
    assert 0.2 * 2 * 2 * observed < metrics["counter_rows_held"] < 0.8 * 2 * 2 * observed
    assert metrics["counter_expert_rows_max"] >= metrics["counter_expert_rows_mean"] > 0
    assert metrics["counter_rows_held"] == pytest.approx(2 * 4 * metrics["counter_expert_rows_mean"])


def _primitives(jaxpr):
    """The name of every primitive in ``jaxpr`` and in the jaxprs its
    equations hold."""
    found = set()
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in sub_jaxprs(eqn):
            found |= _primitives(sub)
    return found


@pytest.mark.parametrize("name,env_args,train_args", [
    ("GeeseNet", {"env": "HungryGeese"}, {"turn_based_training": False}),
    ("TransformerNet", {"env": "Geister", "net": "transformer",
                        "net_args": {"d_model": 32, "n_heads": 2, "n_layers": 2, "memory_len": 8}},
     {"observation": True, "burn_in_steps": 2, "seq_attention": "einsum"}),
    ("HybridNet", _config(HYBRID)["env_args"], {"observation": True, "burn_in_steps": 2}),
], ids=["GeeseNet", "TransformerNet", "HybridNet"])
def test_the_update_is_straight_line_code_of_the_step(name, env_args, train_args):
    """No net's step holds a ``cond``, sentinel on or off: the update runs
    in the step's own computation and the verdict is a select on each leaf
    (a conditional fixes a layout per operand at its boundary and hides the
    clip's norm from the sentinel's: PERF.md, PR 38).  ``HybridNet``'s own
    ``while`` over the expert buffer's passes stays."""
    from benchmark import traffic

    cfg = normalize_args({"env_args": dict(env_args), "train_args": dict(
        train_args, batch_size=2, forward_steps=4, seed=3)})
    args = dict(cfg["train_args"], env=cfg["env_args"])
    random.seed(3)
    np.random.seed(3)
    env = make_env(args["env"])
    module = env.net()
    assert type(module).__name__ == name
    batch = traffic.random_play_batches(env, module, args, 1, 2)[0]
    params = jax.eval_shape(lambda: traffic.seeded_params(module, env, 3))
    for sentinel in (True, False):
        ctx = TrainContext(module, dict(args, sentinel=sentinel), make_mesh({"dp": 1}))
        state = {"params": params, "opt_state": jax.eval_shape(ctx.tx.init, params),
                 "steps": jax.ShapeDtypeStruct((), jnp.int32)}
        found = _primitives(jax.make_jaxpr(ctx._step_fn)(state, batch, jnp.float32(1e-5)).jaxpr)
        assert "cond" not in found, sorted(found)
        assert "select_n" in found and "dot_general" in found     # the walk saw the step
        assert ("while" in found) == (name == "HybridNet")


def test_a_mesh_other_than_dp_1_is_refused_by_name(geister):
    _, args, module, _, _ = geister
    with pytest.raises(ValueError, match=r"HybridNet trains on mesh \{'dp': 1\} only"):
        TrainContext(module, args, make_mesh({"dp": 2}))


def test_an_unknown_layer_kind_is_refused():
    module = HybridNet(num_actions=3, pattern="MX")
    with pytest.raises(ValueError, match="a layer is one of"):
        module.init(jax.random.PRNGKey(0), {"a": jnp.zeros((1, 4))}, None)


def test_step_mode_acts_through_the_inference_model():
    from handyrl_tpu.models import InferenceModel, init_variables

    env = make_env({"env": "TicTacToe", "net": "hybrid", "net_args": dict(NET, memory_len=4)})
    module = env.net()
    model = InferenceModel(module, init_variables(module, env))
    env.reset()
    hidden = model.init_hidden()
    first = model.inference(env.observation(0), hidden)
    assert first["policy"].shape == (9,) and float(first["hidden"]["pos"]) == 1.0
    env.play(4)
    again = model.inference(env.observation(0), first["hidden"])
    fresh = model.inference(env.observation(0), hidden)
    assert not np.allclose(again["policy"], fresh["policy"], atol=1e-5)   # the state matters


# -- the count of its work ----------------------------------------------------


def test_flops_of_the_published_cell_against_a_hand_count():
    with open(os.path.join(REPO, "benchmark", "configs", "nemotron_twotower_30b_a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "workloads", "nemotron_twotower_train_t192.json")) as f:
        cell = json.load(f)
    work = FLOPS.train_update(config, cell)
    # parameters, by hand: the issue's arithmetic
    mamba = 2688 + 2688 * 10304 + 5 * 6144 + 3 * 64 + 4096 + 4096 * 2688
    attention = 2688 + 2 * 2688 * 128 * 34
    experts = 2688 + 2688 * 128 + 128 + 2 * 2688 * 3712 + 2 * 8 * 2688 * 1856
    rest = 270 * 2688 + 2688 + 2688 * 2688 + 2688 + 2688 + 2689 * 216
    assert work["parameters"] == 4 * mamba + attention + 4 * experts + rest == 587_420_376
    # a token is a step that carries an observation: 0.413 of the 184 forward
    # steps, 0.127 of the 8 burn-in steps (the configuration's shapes)
    trained, burn = 64 * 184 * 0.413, 64 * 8 * 0.127
    assert work["tokens"] == pytest.approx(trained + burn)
    # multiply-adds a token, by hand
    ssd = 64.5 * (8 * 128 + 64 * 64) + 2 * 64 * 64 * 128
    m = 2688 * 10304 + 4096 * 2688 + 4 * 6144 + ssd
    e = 2688 * 128 + 2 * 2688 * 3712 + 6 * 8 / 128 * 2 * 2688 * 1856
    a = 2 * 2688 * 128 * 34 + 2 * ((184 * 0.413 + 8 * 0.127 + 1) / 2) * 32 * 128
    per_token = 270 * 2688 + 2688 * 2688 + 2688 * 216 + 4 * m + 4 * e + a
    assert work["flops"] == pytest.approx(2 * per_token * (3 * trained + burn))
    # the issue's 22 TFLOP an update counts all 12,288 steps as tokens
    assert 20e12 < work["flops"] / 0.413 < 24e12
    scopes = FLOPS.scope_work(config, cell)
    assert scopes["experts"]["rows"] == pytest.approx(4 * (trained + burn) * 6 * 8 / 128)
    assert scopes["experts"]["flops"] == pytest.approx(
        scopes["experts"]["rows"] * 3 * 2 * 2 * 2688 * 1856)
    assert scopes["ssd"]["flops"] == pytest.approx(2 * 4 * ssd * (3 * trained + burn))
    assert sum(s["flops"] for s in scopes.values()) < work["flops"]

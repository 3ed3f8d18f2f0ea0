"""The plain reference of ``ouro_2_6b`` on its own, at tiny widths: what an
unobserved step means, each piece against arithmetic written out a second
way in numpy loops, the exit distribution, and the form ``forward_rows``
answers ``harness.judge_forward`` in.  The system against it is
tests/test_looped_net.py.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_ouro_reference.py -q
"""

import inspect
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark import harness  # noqa: E402

ref = harness.load_module(os.path.join(_BENCH, "reference", "ouro_2_6b.py"))

_NET = dict(pattern="*-*-", loops=3, sandwich=True, d_model=12, norm_eps=1e-6,
            n_heads=3, n_kv_heads=3, head_dim=4, rope_theta=100.0, mlp_width=10, memory_len=50)


def _make(seed, **net):
    """(params, config) of a tiny looped trunk with the reference's own names."""
    net = dict(_NET, **net)
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(  # noqa: E731
        rng.randn(*shape) / np.sqrt(shape[-2] if len(shape) > 1 else 1), jnp.float32)
    d, inner = net["d_model"], net["n_heads"] * net["head_dim"]
    dense = lambda a, b: {"kernel": draw(a, b), "bias": draw(b) / 3}  # noqa: E731
    scale = lambda: 1 + draw(d) / 5  # noqa: E731
    params = {"enc1": dense(5, d), "enc2": dense(d, d), "norm_f": scale(),
              "exit_gate": dense(d, 1),
              "policy": dense(d, 4), "value": dense(d, 1), "return_head": dense(d, 1)}
    for i, kind in enumerate(net["pattern"]):
        if kind == "*":
            mixer = {"q": {"kernel": draw(d, inner)}, "k": {"kernel": draw(d, inner)},
                     "v": {"kernel": draw(d, inner)}, "o": {"kernel": draw(inner, d)}}
        else:
            mixer = {"gate": {"kernel": draw(d, net["mlp_width"])},
                     "up": {"kernel": draw(d, net["mlp_width"])},
                     "down": {"kernel": draw(net["mlp_width"], d)}}
        params["layer%d" % i] = {"norm": scale(), "norm_out": scale(), "mixer": mixer}
    return params, {"name": "tiny", "env_args": {"net_args": net}}


def _obs(seed, rows=2, steps=9):
    return {"a": jnp.asarray(np.random.RandomState(seed).randn(rows, steps, 5), jnp.float32)}


def test_an_unobserved_step_is_no_token():
    """The heads at the observed steps are those of the sequence with the
    unobserved steps cut out: no key, no position."""
    params, config = _make(0)
    obs = _obs(1, rows=1)
    observed = jnp.asarray([[0, 1, 0, 1, 1, 0, 0, 1, 1]], jnp.float32)
    keep = np.flatnonzero(np.asarray(observed[0]))
    full = ref.forward(params, obs, observed, config)
    cut = ref.forward(params, {"a": obs["a"][:, keep]}, jnp.ones((1, len(keep))), config)
    for head in ("policy", "value", "return", "exit"):
        np.testing.assert_allclose(full[head][:, keep], cut[head], atol=1e-5)
    # and it is not the sequence as it stands: positions and keys both differ
    whole = ref.forward(params, obs, jnp.ones_like(observed), config)
    assert not np.allclose(whole["policy"][:, keep], cut["policy"], atol=1e-3)


def test_attention_is_causal_rotated_and_dense_by_hand():
    params, config = _make(2)
    net, p = config["env_args"]["net_args"], params["layer0"]["mixer"]
    a = jnp.asarray(np.random.RandomState(3).randn(1, 7, net["d_model"]), jnp.float32)
    got = np.asarray(ref.attention(p, a, jnp.ones((1, 7)), net))
    heads, width = 3, 4

    def turned(x, pos):     # pairs (d, d + 2), angle pos * 100^(-2d/4)
        out = np.array(x)
        for d in range(2):
            angle = pos * 100.0 ** (-2 * d / 4)
            out[d] = x[d] * np.cos(angle) - x[d + 2] * np.sin(angle)
            out[d + 2] = x[d + 2] * np.cos(angle) + x[d] * np.sin(angle)
        return out

    q, k, v = (np.asarray(a[0] @ p[n]["kernel"]).reshape(7, heads, width) for n in "qkv")
    want = np.zeros((7, heads, width))
    for t in range(7):
        for head in range(heads):
            scores = np.array([turned(q[t, head], t) @ turned(k[s, head], s)
                               for s in range(t + 1)]) / np.sqrt(width)      # every step before it
            weights = np.exp(scores - scores.max())
            weights /= weights.sum()
            want[t, head] = sum(w * v[s, head] for s, w in enumerate(weights))
    np.testing.assert_allclose(got[0], want.reshape(7, 12) @ np.asarray(p["o"]["kernel"]), atol=1e-5)


def test_a_layer_is_two_sandwiched_sub_layers_by_hand():
    params, config = _make(4)
    net = config["env_args"]["net_args"]
    z = np.random.RandomState(5).randn(1, 4, net["d_model"]).astype(np.float32)
    attn, mlp = params["layer0"], params["layer1"]
    got = np.asarray(ref.layer(attn, mlp, jnp.asarray(z), jnp.ones((1, 4)), net))
    norm = lambda x, s: x / np.sqrt((x ** 2).mean(axis=-1, keepdims=True) + 1e-6) * np.asarray(s)  # noqa: E731
    mixed = np.asarray(ref.attention(attn["mixer"], jnp.asarray(norm(z, attn["norm"])),
                                     jnp.ones((1, 4)), net))
    x = z + norm(mixed, attn["norm_out"])
    m = norm(x, mlp["norm"])
    gate = m @ np.asarray(mlp["mixer"]["gate"]["kernel"])
    up = m @ np.asarray(mlp["mixer"]["up"]["kernel"])
    down = (gate / (1 + np.exp(-gate)) * up) @ np.asarray(mlp["mixer"]["down"]["kernel"])
    np.testing.assert_allclose(got, x + norm(down, mlp["norm_out"]), atol=1e-5)


def test_the_passes_share_the_weights_and_the_final_norm_closes_each():
    """Three passes by hand from ``layer``: the norm's output feeds the next
    pass, the gates give a distribution over where a token leaves."""
    params, config = _make(6)
    net = config["env_args"]["net_args"]
    obs, observed = _obs(7), jnp.ones((2, 9))
    out = ref.forward(params, obs, observed, config)
    dense = lambda p, x: x @ p["kernel"] + p["bias"]  # noqa: E731
    h = dense(params["enc2"], jnp.maximum(dense(params["enc1"], obs["a"]), 0.0))
    gates = []
    for _ in range(3):
        z = ref.layer(params["layer0"], params["layer1"], h, observed, net)
        z = ref.layer(params["layer2"], params["layer3"], z, observed, net)
        h = ref.rms_norm(z, params["norm_f"], 1e-6)
        gates.append(jax.nn.sigmoid(dense(params["exit_gate"], h)[..., 0]))
    np.testing.assert_allclose(out["policy"], dense(params["policy"], h), atol=1e-5)
    leave = jnp.stack([gates[0], gates[1] * (1 - gates[0]), (1 - gates[0]) * (1 - gates[1])], -1)
    np.testing.assert_allclose(out["exit"], leave, atol=1e-6)
    np.testing.assert_allclose(out["exit"].sum(axis=-1), 1.0, atol=1e-6)
    # one pass fewer is another net
    fewer = ref.forward(params, obs, observed, dict(config, env_args={"net_args": dict(net, loops=2)}))
    assert not np.allclose(fewer["policy"], out["policy"], atol=1e-3)


def test_a_trunk_that_is_not_sandwiched_attention_then_mlp_is_refused():
    params, config = _make(8)
    net = config["env_args"]["net_args"]
    for wrong in (dict(net, pattern="-*-*"), dict(net, sandwich=False)):
        with pytest.raises(ValueError, match="sandwiched layers"):
            ref.forward(params, _obs(9), jnp.ones((2, 9)), {"env_args": {"net_args": wrong}})


@pytest.mark.parametrize("burn_in", [0, 3])
def test_forward_rows_answers_in_the_form_of_the_train_steps_forward(burn_in):
    params, config = _make(10)
    rng = np.random.RandomState(11)
    b, t, players = 2, 9, 2
    batch = {"action": np.zeros((b, t, players, 1)),
             "observation": {"a": rng.randn(b, t, players, 5).astype(np.float32)},
             "observation_mask": (rng.rand(b, t, players, 1) > 0.4).astype(np.float32)}
    out = ref.forward_rows(params, batch, config, burn_in)
    assert set(out) == {"policy", "value", "return"}        # the heads alone: no choices taken
    assert "choices" not in inspect.signature(ref.forward_rows).parameters
    for head, width in (("policy", 4), ("value", 1), ("return", 1)):
        assert out[head].shape == (b, t - burn_in, players, width)
    # a (row, player) is one sequence: the second player's window alone gives its heads
    alone = ref.forward(params, {"a": jnp.asarray(batch["observation"]["a"][:, :, 1])},
                        batch["observation_mask"][:, :, 1, 0], config)
    np.testing.assert_allclose(out["policy"][:, :, 1], alone["policy"][:, burn_in:], atol=1e-6)

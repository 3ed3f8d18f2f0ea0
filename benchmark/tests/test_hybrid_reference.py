"""The plain reference of ``nemotron_twotower_30b_a3b`` on its own, at tiny
widths: what it promises ``harness.judge_forward`` (the choices it is given
are the ones it uses, the gates stay its own), what an unobserved step
means, and each mixer against arithmetic written out a second way in numpy.
The system against it is tests/test_hybrid_net.py.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_hybrid_reference.py -q
"""

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

ref = harness.load_module(os.path.join(_BENCH, "reference", "nemotron_twotower_30b_a3b.py"))

_NET = dict(
    pattern="ME*M", d_model=12, norm_eps=1e-5,
    mamba_heads=2, mamba_head_dim=4, n_groups=1, state_size=3, conv_kernel=4, chunk=4,
    n_experts=6, top_k=2, expert_width=5, shared_width=7, routed_scale=2.5,
    experts_held=3, expert_offset=1, n_heads=4, n_kv_heads=2, head_dim=3, memory_len=50,
)


def _make(seed, **net):
    """(params, config) of a tiny tower with the reference's own names."""
    net = dict(_NET, **net)
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(rng.randn(*shape) / np.sqrt(shape[-2] if len(shape) > 1 else 1), jnp.float32)  # noqa: E731
    d = net["d_model"]
    inner = net["mamba_heads"] * net["mamba_head_dim"]
    conv = inner + 2 * net["n_groups"] * net["state_size"]
    dense = lambda a, b: {"kernel": draw(a, b), "bias": draw(b) / 3}  # noqa: E731
    params = {"enc1": dense(5, d), "enc2": dense(d, d), "norm_f": 1 + draw(d) / 5,
              "policy": dense(d, 4), "value": dense(d, 1), "return_head": dense(d, 1)}
    for i, kind in enumerate(net["pattern"]):
        if kind == "M":
            mixer = {"in_proj": {"kernel": draw(d, inner + conv + net["mamba_heads"])},
                     "conv_kernel": draw(net["conv_kernel"], conv), "conv_bias": draw(conv) / 3,
                     "dt_bias": draw(net["mamba_heads"]), "A_log": draw(net["mamba_heads"]) / 2,
                     "D": 1 + draw(net["mamba_heads"]) / 3, "norm_scale": 1 + draw(inner) / 5,
                     "out_proj": {"kernel": draw(inner, d)}}
        elif kind == "E":
            mixer = {"router": draw(d, net["n_experts"]), "score_bias": jnp.zeros(net["n_experts"]),
                     "w1": draw(net["experts_held"], d, net["expert_width"]),
                     "w2": draw(net["experts_held"], net["expert_width"], d),
                     "shared_up": {"kernel": draw(d, net["shared_width"])},
                     "shared_down": {"kernel": draw(net["shared_width"], d)}}
        else:
            q, kv = net["n_heads"] * net["head_dim"], net["n_kv_heads"] * net["head_dim"]
            mixer = {"q": {"kernel": draw(d, q)}, "k": {"kernel": draw(d, kv)},
                     "v": {"kernel": draw(d, kv)}, "o": {"kernel": draw(q, d)}}
        params["layer%d" % i] = {"norm": 1 + draw(d) / 5, "mixer": mixer}
    return params, {"name": "tiny", "env_args": {"net_args": net}}


def _obs(seed, rows=2, steps=9):
    rng = np.random.RandomState(seed)
    return {"a": jnp.asarray(rng.randn(rows, steps, 5), jnp.float32)}


def test_an_unobserved_step_leaves_every_state_as_it_was():
    """The heads at the observed steps are those of the sequence with the
    unobserved steps cut out."""
    params, config = _make(0)
    obs = _obs(1, rows=1)
    observed = jnp.asarray([[1, 0, 1, 1, 0, 0, 1, 0, 1]], jnp.float32)
    keep = np.flatnonzero(np.asarray(observed[0]))
    full = ref.forward(params, obs, observed, config)
    cut = ref.forward(params, {"a": obs["a"][:, keep]}, jnp.ones((1, len(keep))), config)
    for head in ("policy", "value", "return"):
        np.testing.assert_allclose(full[head][:, keep], cut[head], atol=1e-5)
    for layer in cut["choices"]:
        assert np.array_equal(full["choices"][layer][:, keep], cut["choices"][layer])
        assert not np.asarray(full["choices"][layer])[:, observed[0] == 0].any()


def test_given_choices_are_used_and_the_gates_stay_its_own():
    params, config = _make(2, pattern="E")
    net = config["env_args"]["net_args"]
    h = jnp.asarray(np.random.RandomState(3).randn(2, 5, net["d_model"]), jnp.float32)
    p = params["layer0"]["mixer"]
    own_out, own = ref.experts(p, h, net)
    given = jnp.asarray(np.random.RandomState(4).randint(0, 6, size=own.shape), jnp.int32)
    out, used = ref.experts(p, h, net, given)
    assert np.array_equal(used, given) and not np.allclose(out, own_out)
    # by hand: gates from the scores at the given indices, held experts 1..3 only
    scores = 1 / (1 + np.exp(-np.asarray(h @ p["router"])))
    want = np.square(np.maximum(np.asarray(h @ p["shared_up"]["kernel"]), 0)) @ np.asarray(
        p["shared_down"]["kernel"])
    for n in range(2):
        for t in range(5):
            picked = scores[n, t, np.asarray(given[n, t])]
            for j, e in enumerate(np.asarray(given[n, t])):
                if 1 <= e <= 3:
                    act = np.square(np.maximum(np.asarray(h[n, t] @ p["w1"][e - 1]), 0))
                    want[n, t] += 2.5 * picked[j] / picked.sum() * (act @ np.asarray(p["w2"][e - 1]))
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_the_score_bias_chooses_only():
    params, config = _make(5, pattern="E")
    net, p = config["env_args"]["net_args"], params["layer0"]["mixer"]
    h = jnp.asarray(np.random.RandomState(6).randn(1, 8, net["d_model"]), jnp.float32)
    _, plain = ref.experts(p, h, net)
    out, biased = ref.experts(dict(p, score_bias=jnp.zeros(6).at[4].set(9.0)), h, net)
    assert (np.asarray(biased) == 4).any(axis=-1).all() and not np.array_equal(plain, biased)
    same_out, _ = ref.experts(p, h, net, biased)       # no bias, the same choices: the same mix
    np.testing.assert_allclose(out, same_out, atol=1e-6)


def test_the_recurrence_by_hand():
    """One Mamba-2 layer against loops in numpy."""
    params, config = _make(7, pattern="M")
    net, p = config["env_args"]["net_args"], jax.tree.map(np.asarray, params["layer0"]["mixer"])
    u = np.random.RandomState(8).randn(1, 6, net["d_model"]).astype(np.float32)
    got = ref.mamba(params["layer0"]["mixer"], jnp.asarray(u), jnp.ones((1, 6)), net)
    heads, width, size = 2, 4, 3
    inner = heads * width
    silu = lambda v: v / (1 + np.exp(-v))  # noqa: E731
    proj = u[0] @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[:, :inner], proj[:, inner:-heads], proj[:, -heads:]
    padded = np.concatenate([np.zeros((3, xbc.shape[1]), np.float32), xbc])
    state = np.zeros((heads, width, size))
    rows = []
    for t in range(6):
        conv = silu((padded[t:t + 4] * p["conv_kernel"]).sum(axis=0) + p["conv_bias"])
        x, b, c = conv[:inner].reshape(heads, width), conv[inner:inner + size], conv[inner + size:]
        step = np.log1p(np.exp(dt[t] + p["dt_bias"]))
        y = np.zeros((heads, width))
        for head in range(heads):
            state[head] = np.exp(-step[head] * np.exp(p["A_log"][head])) * state[head] \
                + step[head] * np.outer(x[head], b)
            y[head] = state[head] @ c + p["D"][head] * x[head]
        y = y.reshape(inner) * silu(z[t])
        y = y / np.sqrt((y ** 2).mean() + 1e-5) * p["norm_scale"]       # one group
        rows.append(y @ p["out_proj"]["kernel"])
    np.testing.assert_allclose(got[0], np.stack(rows), atol=2e-5)


def test_attention_is_causal_grouped_and_forgets_past_memory_len():
    params, config = _make(9, pattern="*", memory_len=3)
    net, p = config["env_args"]["net_args"], params["layer0"]["mixer"]
    h = jnp.asarray(np.random.RandomState(10).randn(1, 7, net["d_model"]), jnp.float32)
    got = np.asarray(ref.attention(p, h, jnp.ones((1, 7)), net))
    q = np.asarray(h[0] @ p["q"]["kernel"]).reshape(7, 4, 3)
    k = np.asarray(h[0] @ p["k"]["kernel"]).reshape(7, 2, 3)
    v = np.asarray(h[0] @ p["v"]["kernel"]).reshape(7, 2, 3)
    want = np.zeros((7, 4, 3))
    for t in range(7):
        for head in range(4):
            keys = range(max(0, t - 2), t + 1)          # itself and the two before
            scores = np.array([q[t, head] @ k[s, head // 2] for s in keys]) / np.sqrt(3)
            weights = np.exp(scores - scores.max())
            weights /= weights.sum()
            want[t, head] = sum(w * v[s, head // 2] for w, s in zip(weights, keys))
    np.testing.assert_allclose(got[0], want.reshape(7, 12) @ np.asarray(p["o"]["kernel"]), atol=1e-5)


@pytest.mark.parametrize("burn_in", [0, 3])
def test_forward_rows_takes_the_choices_in_the_form_it_returns_them(burn_in):
    params, config = _make(11)
    rng = np.random.RandomState(12)
    b, t, players = 2, 9, 2
    batch = {"action": np.zeros((b, t, players, 1)),
             "observation": {"a": rng.randn(b, t, players, 5).astype(np.float32)},
             "observation_mask": (rng.rand(b, t, players, 1) > 0.4).astype(np.float32)}
    free = ref.forward_rows(params, batch, config, burn_in)
    chosen = free.pop("choices")
    leaves = jax.tree.leaves(chosen)
    assert len(leaves) == (2 if burn_in else 1)
    assert all(leaf.shape == (b, t - burn_in, players, 2) for leaf in leaves)
    forced = ref.forward_rows(params, batch, config, burn_in, choices=chosen)
    assert harness.choices_agreement(forced.pop("choices"), chosen) == 1.0
    for head in free:
        assert free[head].shape[:3] == (b, t - burn_in, players)
        # where a step was observed: an unobserved one reports no choice, is
        # routed by that when forced, and its output is never read
        seen = batch["observation_mask"][:, burn_in:]
        np.testing.assert_allclose(forced[head] * seen, free[head] * seen, atol=1e-6)
    # other choices on a burn-in step reach the forward steps through the state
    if burn_in:
        other = jax.tree.map(lambda x: x, chosen)
        start = np.array(other["window_start"]["layer1"])
        start[:, 0] = (start[:, 0] + 1) % 6
        other["window_start"]["layer1"] = jnp.asarray(start)
        moved = ref.forward_rows(params, batch, config, burn_in, choices=other)
        assert not np.allclose(moved["policy"] * seen, free["policy"] * seen, atol=1e-6)

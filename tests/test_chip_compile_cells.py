"""Two cells' whole programs compiled for a described (not attached) TPU v5e
by the chip's own compiler and held to the chip's memory, what the
interpreter and the CPU cannot refuse: ``trinity_mini_train_t192``'s train
step (local and global attention) and ``granite_actor_b32``'s streaming
rollout.  Cut from tests/test_chip_compile.py (PR 67), which keeps the
kernels; ``kanana2_train_t192``'s step is in tests/test_chip_compile_steps.py.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from described_v5e import _lowered, _no_compile_cache, v5e, v5e_2x2  # noqa: F401  (fixtures)


def test_the_local_and_global_attention_cells_step_compiles_for_a_v5e_and_fits(v5e_2x2, monkeypatch):
    """``trinity_mini_train_t192``'s train step as its files give it (pattern
    ``W-*EWEWEWE`` at the published widths, B32 x 2p x T192 packed to 8 + 96
    slots, ``remat: block``, bfloat16: two leading layers and a scan over four
    attention-and-experts periods whose one attention layer is told, as data,
    that it is the global or a local one) compiles for a described v5e: the
    forward part's attention core is ``ops/attention_core.py``'s kernel in the
    leading layer (its window and rotation static) and in the period (both
    prefetched beside the rows' counts), each forward, replayed under its
    checkpoint and backward; the grouped kernels take experts 1,024 wide where
    they lie in the periods' stack; the program's peak is under the chip's
    16.9 GB with room (10.92 GB, 7.33 of it the arguments, 162 MB of generated
    code, under the 201 MB jax caches, and 59 s of compile alone on this host,
    PR 58; with a program for each kind, four leading layers unrolled and three
    ``WE`` periods scanned, the same row buffers, it was 10.42 GB, 244 MB and
    85 s), and no whole
    leaf of an expert layer's weights, or of their stack, is copied."""
    import json
    import os

    from handyrl_tpu.models.hybrid import GQA_SCOPE

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "workloads", "trinity_mini_train_t192.json")) as f:
        cell = json.load(f)
    with open(os.path.join(bench, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    _, lowered = _lowered(v5e_2x2, 1, dict(config["env_args"]),
                          dict(config["train_args"], **cell["train_args"]), packed=(8, 96))
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert 7.0e9 < memory.argument_size_in_bytes < memory.peak_memory_in_bytes < 13.0e9
    assert memory.generated_code_size_in_bytes < 201e6      # what jax's compile cache takes
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line]
    cores = [line for line in calls if "/" + GQA_SCOPE + "/" in line]
    # the leading layer's and the period's, each forward, replayed and backward
    assert len(cores) == 6 and sum("/while/" in line for line in cores) == 3
    assert len(calls) - len(cores) == 20        # the period's grouped products, both window parts
    assert "f32[64,32,96,104]" not in text      # no score tile outside the kernel
    held = config["env_args"]["net_args"]["experts_held"]
    copies = re.compile(
        r"= (bf16|f32)\[(4,)?%d,(2048,2048|1024,2048)\]\S* (copy|copy-start)\(" % held)
    found = [line.strip()[:160] for line in text.splitlines() if copies.search(line)]
    assert not found, found[:3]


@pytest.mark.parametrize("compiled", [False, pytest.param(True, marks=pytest.mark.slow)],
                         ids=["lowered", "compiled"])
def test_the_mamba_cells_step_for_a_v5e_holds_the_window_kernel(v5e_2x2, monkeypatch, compiled):
    """``nemotron_twotower_train_t192``'s train step as its files give it
    (pattern ``MEMEM*EME`` at the published widths, B32 x 2p x T192 packed to
    8 + 96 slots, ``remat: block``, bfloat16), lowered for a described v5e:
    each of the four Mamba layers' 96-step part runs ``ops/ssd.py``'s window
    kernel forward, replayed under its checkpoint and backward, twelve Mosaic
    calls under the ``ssd`` scope the benchmark times (the step drops the
    part's last state, so the state's own kernel is in no program), and the 8 burn-in
    steps keep the lines (what the recorder will say as
    ``model.ssd_window_path``).  ``compiled`` (``slow``: 75 s of compile on
    eight cores, PR 69) also holds the program to the chip: 10.47 GB at its
    peak (the parent's 10.30), 7.08 of it the arguments."""
    import json

    from handyrl_tpu.ops import ssd

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "workloads", "nemotron_twotower_train_t192.json")) as f:
        cell = json.load(f)
    with open(os.path.join(bench, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")   # not the interpreter
    ssd.WINDOW_PATHS.clear()
    _, lowered = _lowered(v5e_2x2, 1, dict(config["env_args"]),
                          dict(config["train_args"], **cell["train_args"]), packed=(8, 96))
    chosen = {made["length"]: made["path"] for made in ssd.WINDOW_PATHS.values()}
    ssd.WINDOW_PATHS.clear()
    assert chosen == {8: "lines", 96: "kernel"}, chosen
    if not compiled:
        names = re.findall(r'stablehlo\.custom_call @tpu_custom_call.*?kernel_name = "(\w+)"',
                           lowered.as_text())
        assert names.count("_window_forward_kernel") == 8, names
        assert names.count("_window_backward_kernel") == 4, names
        assert "_window_state_kernel" not in names      # the step drops a part's last state
        return
    program = lowered.compile()
    calls = [line for line in program.as_text().splitlines()
             if "custom-call(" in line and "tpu_custom_call" in line and "/ssd/" in line]
    assert len(calls) == 12, len(calls)
    memory = program.memory_analysis()
    assert 7.0e9 < memory.argument_size_in_bytes < memory.peak_memory_in_bytes < 11.5e9


def test_actor_cell_rollout_compiles_for_a_v5e_and_fits_with_its_state_donated(v5e, monkeypatch):
    """The streaming rollout of the benchmark's actor cell at its own sizes
    (32 Geister lanes x 2 players, 16 steps, one period of the published
    widths in bfloat16) compiles for a v5e with the routed experts' kernel in
    it, and fits: the weights (9.14 GB) and one copy of the per-row state
    (2.58 GB); the donated hidden tree is aliased through the scan and the
    commit's select fused, so the temporaries stay under a gigabyte.  ISSUE
    44's rule: over 15.5 GB the cell would run 16 lanes."""
    import json

    from handyrl_tpu.envs import make_env
    from handyrl_tpu.runtime.device_rollout import build_streaming_fn

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs", "granite_4_0_h_small.json")) as f:
        config = json.load(f)
    with open(os.path.join(here, "benchmark", "workloads", "granite_actor_b32.json")) as f:
        cell = json.load(f)["train_args"]
    lanes, k = cell["device_rollout_games"], cell["device_replay_k_steps"]
    env = make_env(config["env_args"])
    module, venv = env.net(), env.vector_env()
    env.reset()
    obs = jax.tree.map(lambda x: jnp.asarray(x)[None], env.observation(env.players()[0]))
    described = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e), tree)
    params = described(jax.eval_shape(
        lambda key: module.init(key, obs, module.initial_state((1,)))["params"],
        jax.random.PRNGKey(0)))
    vstate = described(jax.eval_shape(lambda key: venv.init(lanes, key), jax.random.PRNGKey(0)))
    hidden = described(jax.eval_shape(lambda: module.initial_state((lanes, venv.num_players))))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # the kernel, not its interpreter
    fn = build_streaming_fn(venv, module, lanes, k, use_observe_mask=cell["observation"],
                            counters=True)
    compiled = fn.lower(params, vstate, hidden, key).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # ~4.5 rows a held expert: row buffers of 56 blocks of 16, not 39 of 128 (PR 46)
    assert "[896,4096]" in text and "[4992," not in text
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - memory.alias_size_in_bytes)
    assert 11.5e9 < held < 13.0e9, held                 # read 12.09 GB (PR 45); on the chip 11.76 in use
    assert memory.alias_size_in_bytes > 2.5e9           # the hidden tree, donated
    assert memory.temp_size_in_bytes < 1.0e9
    # one player a lane observes (PR 45): a Mamba-2 layer's state for all lanes
    # and both players is stepped where it lies, the acting player's row of
    # each lane through ``ops/ssd.py``'s kernel (which sees it as lanes x
    # players x (heads x head_dim) x S: a bitcast).  Nothing else yields or
    # reads an array of its whole shape: no copy, no select, no multiply, no
    # fusion, no scatter
    rows = module.mamba_heads * module.mamba_head_dim
    whole = ("f32[%d,%d,%d,%d,%d]" % (lanes, venv.num_players, module.mamba_heads,
                                      module.mamba_head_dim, module.state_size),
             "f32[%d,%d,%d,%d]" % (lanes, venv.num_players, rows, module.state_size))
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\(?[^=]*?\)?) ([\w-]+)\((.*)$", text, flags=re.M)
    touch = lambda s: any(leaf in s for leaf in whole)  # noqa: E731
    yields = {op for shape, op, _ in ops if touch(shape) and not shape.startswith("(s32[]")}
    reads = {op for shape, op, rest in ops
             if touch(rest.split(", metadata=")[0].split(", custom_call_target=")[0])
             and not touch(shape)}
    steps = [rest for shape, op, rest in ops if op == "custom-call" and touch(shape)]
    assert len(steps) == module.pattern.count("M") == 9, len(steps)
    assert yields == {"custom-call", "get-tuple-element", "parameter", "bitcast"}, yields
    assert not reads, reads
    # and the kernel writes the buffer it read: the scan's carry is its output
    assert all("output_to_operand_aliasing" in rest for rest in steps), steps[0][:400]

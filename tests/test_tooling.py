"""Offline tooling tests: StableHLO export, SWA averaging, log plotters.

Parity surface: reference scripts/ (aux_swa.py, make_onnx_model.py,
win_rate/loss/stats plotters) per SURVEY.md §2.3.
"""

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Environmental, reproduces at the seed commit on this container's jax
# 0.4.37: models/export.py drives ``jax.export`` (symbolic_shape /
# SymbolicScope / export / deserialize), which this jax exposes only as
# ``jax.experimental.export`` — ``AttributeError: module 'jax' has no
# attribute 'export'`` before any model code runs.  Skip (not fail) where
# the public module is absent.
needs_jax_export = pytest.mark.skipif(
    not hasattr(jax, "export"),
    reason="jax.export unavailable on this jax (< 0.5); StableHLO export "
    "tooling needs it (seed-reproducing environmental failure)",
)


def _model(env_name):
    from handyrl_tpu.envs import make_env
    from handyrl_tpu.models import InferenceModel, init_variables

    env = make_env({"env": env_name})
    module = env.net()
    variables = init_variables(module, env)
    return env, module, variables, InferenceModel(module, variables)


@needs_jax_export
@pytest.mark.parametrize("env_name", ["TicTacToe", "Geister"])
def test_export_roundtrip(env_name, tmp_path):
    from handyrl_tpu.models import ExportedModel, export_model
    from handyrl_tpu.utils import tree_stack

    env, module, variables, model = _model(env_name)
    env.reset()
    obs = env.observation(env.players()[0])
    path = str(tmp_path / f"{env_name}.hlo")
    export_model(module, variables, obs, path)

    ex = ExportedModel(path)
    o1 = model.inference(obs, model.init_hidden())
    o2 = ex.inference(obs, ex.init_hidden())
    np.testing.assert_allclose(o1["policy"], o2["policy"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(o1["value"], o2["value"], rtol=1e-4, atol=1e-5)

    # dynamic batch dimension: batch-3 through the same artifact
    obs_b = tree_stack([obs, obs, obs])
    hidden = ex.init_hidden()
    hidden_b = None if hidden is None else tree_stack([hidden] * 3)
    out = ex.inference_batch(obs_b, hidden_b)
    assert np.asarray(out["policy"]).shape[0] == 3


@needs_jax_export
def test_exported_model_plays_matches(tmp_path):
    from handyrl_tpu.runtime.evaluation import exec_match, load_model_agent
    from handyrl_tpu.agents import RandomAgent
    from handyrl_tpu.models import export_model

    env, module, variables, model = _model("TicTacToe")
    env.reset()
    path = str(tmp_path / "ttt.hlo")
    export_model(module, variables, env.observation(0), path)

    agents = {0: load_model_agent(path, env), 1: RandomAgent()}
    outcome = exec_match(env, agents)
    assert outcome is not None and set(outcome) == {0, 1}


def test_swa_script(tmp_path):
    from handyrl_tpu.runtime.checkpoint import load_params, model_path, save_params
    from handyrl_tpu.utils import tree_map

    env, module, variables, model = _model("TicTacToe")
    model_dir = tmp_path / "models"
    base = variables["params"]
    for epoch, scale in ((1, 1.0), (2, 2.0), (3, 3.0)):
        save_params(str(model_path(str(model_dir), epoch)), tree_map(lambda x: np.asarray(x) * scale, base))

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "aux_swa.py"), str(model_dir), "3", "3"],
        capture_output=True,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr
    swa = load_params(str(model_dir / "swa.ckpt"), base)
    # average of 1x, 2x, 3x = 2x
    np.testing.assert_allclose(
        np.asarray(next(iter(jax_leaves(swa)))),
        np.asarray(next(iter(jax_leaves(base)))) * 2.0,
        rtol=1e-5,
    )


def jax_leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def test_logparse_both_formats(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from _logparse import parse_records

    metrics = tmp_path / "metrics.jsonl"
    with open(metrics, "w") as f:
        for e in range(3):
            f.write(json.dumps({"epoch": e, "win_rate": {"total": 0.5 + 0.1 * e},
                                "loss": {"p": 0.4 - 0.1 * e, "v": 0.3},
                                "generation_mean": 0.0, "generation_std": 0.9}) + "\n")
    recs = parse_records(str(metrics))
    assert len(recs) == 3 and recs[2]["win_rate"]["total"] == 0.7

    log = tmp_path / "train.log"
    log.write_text(
        "started server\n"
        "epoch 0\n"
        "win rate = 0.520 (13.0 / 25)\n"
        "generation stats = 0.100 +- 0.935\n"
        "loss = ent:1.418 p:0.375 r:0.000 total:0.590 v:0.311\n"
        "updated model(1)\n"
        "epoch 1\n"
        "win rate (random) = 0.769 (10.0 / 13)\n"
        "generation stats = 0.200 +- 0.866\n"
        "loss = ent:1.453 p:0.354 r:0.000 total:0.531 v:0.273\n"
        "updated model(331)\n"
    )
    recs = parse_records(str(log))
    assert len(recs) == 2
    assert recs[0]["win_rate"]["total"] == 0.520
    assert recs[1]["win_rate"]["random"] == 0.769
    assert recs[1]["loss"]["p"] == 0.354
    assert recs[1]["steps"] == 331
    assert recs[0]["generation_mean"] == 0.1


@pytest.mark.parametrize("script", ["win_rate_plot.py", "loss_plot.py", "stats_plot.py"])
def test_plot_scripts(script, tmp_path):
    metrics = tmp_path / "metrics.jsonl"
    with open(metrics, "w") as f:
        for e in range(5):
            f.write(json.dumps({"epoch": e, "win_rate": {"total": 0.5, "random": 0.6},
                                "loss": {"p": 0.4, "v": 0.3, "total": 0.7},
                                "generation_mean": 0.1 * e, "generation_std": 0.5}) + "\n")
    out = tmp_path / "plot.png"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", script), str(metrics), str(out)],
        capture_output=True,
        text=True,
        env={**os.environ, "PYTHONPATH": REPO, "MPLBACKEND": "Agg"},
    )
    assert proc.returncode == 0, proc.stderr
    assert out.exists() and out.stat().st_size > 1000


@pytest.mark.parametrize("env_name", ["TicTacToe", "Geister"])
def test_savedmodel_roundtrip(env_name, tmp_path):
    """jax2tf SavedModel bridge: outputs (incl. recurrent hidden) match the
    live model, and the batch dimension stays polymorphic."""
    pytest.importorskip("tensorflow")
    from handyrl_tpu.models.export import SavedModelModel, export_savedmodel
    from handyrl_tpu.utils import tree_map, tree_stack

    env, module, variables, model = _model(env_name)
    env.reset()
    obs = env.observation(env.players()[0])
    path = str(tmp_path / f"{env_name}.tf")
    export_savedmodel(module, variables, obs, path)

    sm = SavedModelModel(path)
    o1 = model.inference(obs, model.init_hidden())
    o2 = sm.inference(obs, sm.init_hidden())
    np.testing.assert_allclose(o1["policy"], o2["policy"], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(o1["value"], o2["value"], rtol=1e-4, atol=1e-5)
    if o1.get("hidden") is not None:
        for a, b in zip(
            jax.tree.leaves(o1["hidden"]), jax.tree.leaves(o2["hidden"])
        ):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    obs_b = tree_stack([obs, obs, obs])
    hidden = sm.init_hidden()
    hidden_b = None if hidden is None else tree_stack([hidden] * 3)
    out = sm.inference_batch(obs_b, hidden_b)
    assert np.asarray(out["policy"]).shape[0] == 3


@pytest.mark.parametrize("env_name", ["TicTacToe", "Geister"])
def test_onnx_roundtrip(env_name, tmp_path):
    """Real .onnx artifact (jaxpr -> torch bridge, models/torch_export.py)
    loaded through onnxruntime matches the live model — the reference's
    exact deployment path (scripts/make_onnx_model.py:28-58,
    evaluation.py:287-353).  The EXPORT side runs and is verified
    in-image (tests/test_export_onnx_contract.py); onnxruntime execution
    is what needs the optional dep, so this skips where it is absent —
    except in the CI extras job (HANDYRL_REQUIRE_EXTRAS), which exists to
    execute this leg and must FAIL loudly on a missing/broken dep."""
    if os.environ.get("HANDYRL_REQUIRE_EXTRAS"):
        import onnxruntime  # noqa: F401
        import torch  # noqa: F401
    else:
        pytest.importorskip("torch")  # the export side runs on torch
        pytest.importorskip("onnxruntime")
    from handyrl_tpu.models.export import OnnxModel, export_onnx

    env, module, variables, model = _model(env_name)
    env.reset()
    obs = env.observation(env.players()[0])
    path = str(tmp_path / f"{env_name}.onnx")
    export_onnx(module, variables, obs, path)

    om = OnnxModel(path)
    o1 = model.inference(obs, model.init_hidden())
    o2 = om.inference(obs, om.init_hidden())
    np.testing.assert_allclose(o1["policy"], o2["policy"], rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(o1["value"], o2["value"], rtol=1e-3, atol=1e-4)
    if o1.get("hidden") is not None:
        for a, b in zip(
            jax.tree.leaves(o1["hidden"]), jax.tree.leaves(o2["hidden"])
        ):
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


# -- the docs name only what exists --------------------------------------------

DOC_FILES = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    "docs/" + name for name in os.listdir(os.path.join(REPO, "docs")) if name.endswith(".md")
)
_TREE_PATH = re.compile(r"^(?:handyrl_tpu|tools|scripts|tests|benchmark|docs)/[\w./-]*$")
_BARE_FILE = re.compile(r"^[\w.-]+\.(?:py|json)$")
_COMMAND = re.compile(r"\bpython3?\s+(-m\s+)?([\w./-]+)")
# written by a run, never committed
RUN_TIME_FILES = {"MANIFEST.json"}


@pytest.fixture(scope="module")
def repo_files():
    """What git would commit; where the checkout is no repository (the
    chip machine's copy), what is on disk."""
    listed = subprocess.run(["git", "ls-files"], cwd=REPO, capture_output=True, text=True)
    if listed.returncode == 0 and listed.stdout:
        return set(listed.stdout.split("\n")) - {""}
    return {
        os.path.relpath(os.path.join(folder, name), REPO)
        for folder, _, names in os.walk(REPO) for name in names
    }


def _missing(text, files):
    """Commands and backticked paths in ``text`` that name nothing in
    ``files``: ``python <file>.py``, ``python -m <module of this repo>``,
    a path under one of the tree's directories, and a bare ``*.py`` or
    ``*.json`` name, which may be a file anywhere in the tree.  Globs,
    ``<placeholders>`` and ``{a,b}`` sets are skipped."""
    folders = {path[:end] for path in files for end in range(len(path)) if path[end] == "/"}
    names = {os.path.basename(path) for path in files} | RUN_TIME_FILES

    def exists(path):
        return path in files or path.rstrip("/") in folders

    missing = []
    for dash_m, target in _COMMAND.findall(text):
        if target.endswith((".", "/")):      # a placeholder follows
            continue
        if dash_m:
            path = target.replace(".", "/")
            if path.split("/")[0] in folders and not any(
                exists(path + tail) for tail in (".py", "/__main__.py", "/__init__.py")
            ):
                missing.append("python -m " + target)
        elif target.endswith(".py") and not exists(os.path.normpath(target)):
            missing.append("python " + target)
    for quoted in re.findall(r"`([^`\n]+)`", text):
        for token in quoted.split():
            if any(c in token for c in "*?[<{"):
                continue
            path = re.split(r"::|:\d|#", token.strip(".,;()"))[0]
            if _TREE_PATH.match(path) and not exists(path):
                missing.append(path)
            elif _BARE_FILE.match(path) and path not in names:
                missing.append(path)
    return sorted(set(missing))


@pytest.mark.parametrize("doc", DOC_FILES)
def test_docs_name_only_files_and_commands_that_exist(doc, repo_files):
    """A doc that sends a reader to a deleted script fails here (the
    benchmark the README described for twenty-nine PRs was one)."""
    with open(os.path.join(REPO, doc)) as f:
        assert _missing(f.read(), repo_files) == []


def test_the_rollout_plane_imports_none_of_its_hosts():
    """``runtime/rollout_plane.py`` is driven by ``Learner`` and by
    ``actor_loop``; what it needs of a host it is handed as callables."""
    import ast

    path = os.path.join(REPO, "handyrl_tpu", "runtime", "rollout_plane.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported |= {(node.module or "").split(".")[-1]} | {a.name for a in node.names}
        elif isinstance(node, ast.Import):
            imported |= {a.name.split(".")[-1] for a in node.names}
    assert not imported & {"learner", "trainer", "server", "actor_host"}
    assert {"device_rollout", "mesh", "faults", "trace"} <= imported

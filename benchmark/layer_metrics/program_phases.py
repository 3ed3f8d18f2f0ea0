"""Not a metric: what the readers of the phases inside the device programs
share (``update_step_share``, ``sample_step_share``, ``rollout_env_share``).

The program names its phases as it names its programs: a ``jax.named_scope``
whose name is a module-level constant beside the ``jax.jit`` call
(``train_step.UPDATE_SCOPE``, ``device_replay.SAMPLE_SCOPE``,
``device_rollout.STREAM_SCOPES``, ...), which a device profile shows as a path
component of an op's ``op_name``.  A reader imports the constant; a program
that has none yet (an older commit) answers ``None``.

The cells' files list no such scope (``workloads/*.json`` are not this
file's to edit), so ``harness.reduce_profile`` did not read them: ``phases``
loads the run's profile once more with every scope the program names, cuts it
at the same two marker spans and reduces it with the same reducer.  One pass
a run, whichever reader asks first; what it found and the seconds it took go
to the run's notes (``phases``, ``phases_pass_s``).

An op of the backward pass carries a ``transpose(`` component that jax
writes itself.  The pass tags those ops, so that the notes also hold the
train program's seconds forward and backward (``phases_backward``): no
metric, the builder's line.
"""

import time

from benchmark import harness, trace_reduce
from handyrl_tpu.parallel import train_step
from handyrl_tpu.runtime import device_replay, device_rollout

# (owner, constant): a scope's name, or a tuple of them, as the program has it
UPDATE = (train_step, "UPDATE_SCOPE")
SAMPLE = (device_replay, "SAMPLE_SCOPE")
SAMPLE_PARTS = (device_replay, "SAMPLE_PART_SCOPES")
ROLLOUT_ENV = (device_rollout, "ENV_SCOPES")
ROLLOUT = (device_rollout, "STREAM_SCOPES")
# a component no program writes: the pass puts it in front of the ``op_name``
# of every op that has jax's own ``transpose(`` in it
BACKWARD, BACKWARD_MARK = "backward_pass", "transpose("


def names(group):
    """The scope names the program gives under ``group``'s constant; none
    where it lacks the constant."""
    owner, constant = group
    found = getattr(owner, constant, ())
    return [found] if isinstance(found, str) else list(found)


def read(run):
    """Asked as a metric (``tests/test_layer_readers.py`` walks every file of
    this directory but ``loop_program.py``), it is none."""
    return None


def phases(run):
    """scope -> ``{"seconds", "ops"}`` inside the traced window, for every
    scope the program names and at least one op carries; None where the run
    has no reduced profile or the program no scope."""
    if "phases" in run.notes:
        return run.notes["phases"]
    scopes = [name for group in (UPDATE, SAMPLE, SAMPLE_PARTS, ROLLOUT) for name in names(group)]
    if run.reduced is None or not run.xplane or not scopes:
        return None
    t0 = time.monotonic()
    trace = trace_reduce.load_xplane(run.xplane, scopes=scopes + [BACKWARD])
    for device in trace["devices"].values():
        device["op_names"] = [
            BACKWARD + "/" + name if BACKWARD_MARK in name else name
            for name in device["op_names"]]
    begin = [s for s in trace["host"] if s[0] == harness.WINDOW_BEGIN]
    end = [s for s in trace["host"] if s[0] == harness.WINDOW_END]
    window = (begin[0][2], end[-1][1]) if begin and end else None
    found = trace_reduce.reduce_trace(trace, window)["scopes"]
    backward = found.pop(BACKWARD, None)
    program = train_program(run)
    if backward and program:
        run.notes["phases_backward"] = {
            "program_s": program["seconds"], "backward_s": backward["seconds"],
            "backward_ops": backward["ops"]}
    run.notes["phases"] = found
    run.notes["phases_pass_s"] = time.monotonic() - t0
    return found


def train_program(run):
    """The program that holds the SGD update: the cell's ``train`` role, or
    the loop's fused sample+train program."""
    program = run.program("train")
    if program is not None:
        return program
    shared = harness.load_module(run.path("layer_metrics", "loop_program.py"))
    return shared.find(run, device_replay, "TRAIN_PROGRAM")


def share(run, program, group, noted=()):
    """Percent of ``program``'s device seconds under the scopes of ``group``
    together (they do not nest); None where the program or every one of the
    scopes is missing.  Each scope of ``group`` and of the groups ``noted``
    goes to the run's notes (``phases_ms_per_run``) in milliseconds a run of
    the program: its share of the program's seconds times the program's
    time a whole run."""
    found = phases(run)
    if not found or program is None or not program["seconds"]:
        return None
    mine = [found[name] for name in names(group) if name in found]
    if not mine:
        return None
    if program["whole_runs"]:
        ms_per_run = 1e3 * program["whole_seconds"] / program["whole_runs"]
        run.notes.setdefault("phases_ms_per_run", {}).update({
            name: ms_per_run * found[name]["seconds"] / program["seconds"]
            for one in (group,) + tuple(noted) for name in names(one) if name in found})
    return 100.0 * sum(v["seconds"] for v in mine) / program["seconds"]

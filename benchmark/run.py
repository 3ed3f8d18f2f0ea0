"""Run one cell of BENCHMARK.json once, in this process, on this machine.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and the program's own output on stderr, and as the LAST
line of stdout one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, traced ``breakdown``,
and last ``compared`` (each number the run compared beside its limit,
printed again as the last lines of stderr).  Without the TPU chips the cell
asks for it exits 2, and with a program that lacks the configuration's
``module`` it exits 3, and prints no result.  ``--rehearse`` walks the same control flow on
whatever device jax has (the CPU, in tests): it prints counts only, says
``correct: false`` and exits non-zero, so no CPU number can pass for a
device metric.  See README.md for the files a cell is made of.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

EXIT_NO_DEVICE, EXIT_INCORRECT, EXIT_REHEARSAL, EXIT_NO_PROGRAM = 2, 1, 4, 3


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--root", default=HERE,
                        help="where workloads/, configs/, runners/ ... are found")
    parser.add_argument("--rehearse", action="store_true",
                        help="control flow only, on any device; never a result")
    opts = parser.parse_args(argv)

    try:
        import handyrl_tpu  # noqa: F401  (the system under test)
    except ImportError as exc:
        print(f"benchmark: the program is not in this checkout: {exc}", file=sys.stderr)
        return EXIT_NO_PROGRAM

    import jax

    from benchmark import harness
    from handyrl_tpu.utils.compile_cache import CompileCounters, enable_compile_cache

    run = harness.Run(os.path.abspath(opts.root), opts.workload, opts.seed, opts.seconds,
                      bool(opts.trace), opts.rehearse, T_PROCESS)
    # a hung run must end as a failure inside the driver's limit, with
    # every thread's stack on stderr
    faulthandler.dump_traceback_later(run.seconds_left(), exit=True, file=sys.__stderr__)
    devices = jax.devices()
    if not opts.rehearse and (devices[0].platform != "tpu" or len(devices) < run.chips):
        print(f"benchmark: {run.workload} needs {run.chips} TPU chip(s); jax found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return EXIT_NO_DEVICE
    if len(devices) < run.chips:
        print(f"benchmark: rehearsal needs {run.chips} devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count)", file=sys.stderr)
        return EXIT_NO_DEVICE
    run.devices = devices[:run.chips]
    # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache: a
    # fixed path, so every run of a cell after its first loads its programs
    cache_dir = enable_compile_cache()
    run.compile = CompileCounters()
    shutil.rmtree(run.out_dir, ignore_errors=True)
    os.makedirs(run.out_dir)
    print(f"benchmark: {run.workload} on {len(run.devices)} x "
          f"{devices[0].device_kind}; compile cache {cache_dir}; out {run.out_dir}",
          file=sys.stderr, flush=True)

    log_path = os.path.join(run.out_dir, "log.txt")
    stdout, stderr, cwd = sys.stdout, sys.stderr, os.getcwd()
    try:
        with open(log_path, "w") as sink:
            sys.stdout = sys.stderr = harness.Tee(sink)
            os.chdir(run.out_dir)    # the program writes relative paths
            try:
                run.runner().run(run)
            finally:
                os.chdir(cwd)
                sys.stdout, sys.stderr = stdout, stderr
    except harness.NoProgram as exc:
        faulthandler.cancel_dump_traceback_later()
        print(f"benchmark: {exc}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    markers = harness.log_has_fallback(log_path)
    run.checks["no_fallback_marker"] = not markers
    if markers:
        run.notes["fallback_markers"] = markers

    if run.trace:
        run.checks["profile_collected"] = run.xplane is not None
    if run.xplane:
        t0 = time.monotonic()
        try:
            harness.reduce_profile(run)
        except ValueError as exc:       # a CPU rehearsal has no device plane
            run.notes["trace_not_reduced"] = str(exc)
        # what sizes the next failure: stop() and the reducer both go with
        # the bytes, and the bytes with the updates the window held
        updates = run.counters.get("updates")
        run.notes.update(
            reduce_s=time.monotonic() - t0, updates_in_trace=updates,
            bytes_per_update=run.notes["profile_bytes"] / updates if updates else None)
    faulthandler.cancel_dump_traceback_later()

    on_chip = run.devices[0].platform == "tpu" and not opts.rehearse
    run.checks["device_is_tpu"] = on_chip
    if run.trace:
        run.checks["device_ran"] = bool(run.reduced and run.reduced["busy_s"] > 0)
    units = harness.units(run)
    group = "per_layer" if run.trace else "end_to_end"
    metrics = {}
    for name in run.metric_names(group):
        if run.trace:
            value = harness.load_module(run.path("layer_metrics", name + ".py")).read(run)
        else:
            value = run.values[name]
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units[name]}
    if not on_chip:
        # a rehearsal walked the readers; it reports which answered, never what
        run.notes["metrics_answered"] = sorted(metrics)
        metrics = {}
    correct = all(run.checks.values()) and run.failed == 0
    result = {
        "correct": bool(correct), "attempted": int(run.attempted),
        "failed": int(run.failed), "metrics": metrics,
        "device": harness.device_record(run),
    }
    if run.trace and run.reduced is not None and on_chip:
        from benchmark import trace_reduce

        result["breakdown"] = trace_reduce.breakdown(run.reduced)
    # each number compared beside its limit: the line's last key, and the
    # last lines of stderr
    result["compared"] = dict(run.compared, failed=[int(run.failed), 0])
    run.notes["seconds_left"] = run.seconds_left()
    # an earlier line: what the last line has no key for
    print(json.dumps({
        "workload": run.workload, "seed": run.seed, "window_s": run.window_s,
        "checks": run.checks, "counters": run.counters, "notes": run.notes,
        "setup_compile": run.setup_compile,
    }, default=float))
    print(json.dumps(result))
    sys.stdout.flush()
    for name, (number, limit) in result["compared"].items():
        print(f"benchmark: compared {name} {number:.6g} limit {limit:.6g}", file=sys.stderr)
    failing = sorted(name for name, held in run.checks.items() if not held)
    print(f"benchmark: correct {correct}; checks that fail: {failing}", file=sys.stderr)
    if opts.rehearse:
        return EXIT_REHEARSAL
    return 0 if correct else EXIT_INCORRECT


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (flushers, receivers) must not hold the
    # interpreter open past the result
    os._exit(code)

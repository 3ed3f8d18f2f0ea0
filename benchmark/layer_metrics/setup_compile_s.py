"""Seconds jax spent tracing, lowering and compiling (or loading from the
persistent cache) before the window opened, summed over threads: the
program's ``CompileCounters`` at the window's start."""


def read(run):
    return run.setup_compile["compile_s"] if run.setup_compile else None

"""Seconds of set-up in which at least one thread was tracing, lowering,
building or loading a program: the union of the intervals of the program's
compile records (``CompileCounters.programs()``: one record for each of jax's
compile events, with the program's name, the phase, the thread and the start
on the spans' clock) between the process's start and the window's.  A part of
``setup_s``, which ``setup_compile_s`` is not: that one adds the same events'
seconds over threads and over nesting levels.

What the four ``setup_*`` readers share is here too (the other three load
this file): the records cut at the window, the set-up phases
(``trace_phase``: the program's list, and whatever of ``run.spans`` says
``phase``), and the tables that go to the run's notes from whichever of the
four runs first: ``setup_s`` (the run's own), ``setup_programs`` (the eight programs with the most seconds
before the window), ``setup_misses`` (every build before it that found no
cache entry), ``setup_phases``, ``after_window_programs`` (what was traced,
built or loaded once the window had closed: the judge's programs, out of the
same 330 s) and ``compile_records_dropped``.

A program without ``programs()`` (an older commit under these files), or a
run whose window never opened, answers None."""

from benchmark import trace_reduce

TOP = 8


def setup(run):
    """``{"lo", "hi", "records", "phases"}``: the set-up's bounds on the
    monotonic clock, the compile records that count once (no nested trace, no
    folded remainder) and the ``setup.*`` phases; None where the program keeps
    no records or the window never opened."""
    programs = getattr(run.compile, "programs", None)
    if programs is None or run.t_window is None:
        return None
    records = [r for r in programs() if not r.get("nested")]
    found = {"lo": run.t_process, "hi": run.t_window, "records": records,
             "phases": _phases(run)}
    if "setup_programs" not in run.notes:
        before = [r for r in records if r["t_mono"] < run.t_window]
        after = [r for r in records if r["t_mono"] >= run.t_window + run.window_s]
        run.notes.update(
            setup_s=run.t_window - run.t_process,      # this run's own: a traced run prints no end-to-end metric
            setup_programs=table(before),
            setup_misses=[
                {"program": r["program"], "s": r["dur_s"], "thread": r["thread"]}
                for r in before if r["phase"] == "backend" and r.get("cache") == "miss"],
            setup_phases=[
                {"phase": p["name"], "s": p["dur_s"], "thread": p.get("thread")}
                for p in found["phases"] if p["t_mono"] < run.t_window],
            after_window_programs=table(after),
            compile_records_dropped=getattr(run.compile, "dropped", 0),
        )
    return found


def _phases(run):
    """The process's phases: the tracer's own list (it is kept whether or not
    a tracer was configured, and two of the runners keep only the window's
    spans in ``run.spans``), and any record of ``run.spans`` that says
    ``phase``, each once."""
    from handyrl_tpu.utils import trace

    kept = trace.phases() + [s for s in run.spans if s.get("phase")]
    seen, out = set(), []
    for p in kept:
        key = (p["name"], p.get("thread"), round(p["t_mono"], 6))
        if key not in seen and p["name"].startswith("setup."):
            seen.add(key)
            out.append(p)
    return sorted(out, key=lambda p: p["t_mono"])


def _name(program):
    """jax names a trace by the function (``_step``) and the lowering and the
    backend's part by the module (``jit(_step)``): one row for both."""
    return program[4:-1] if program.startswith("jit(") and program.endswith(")") else program


def table(records, top=TOP):
    """Seconds by program and thread, the most first: ``trace_s``,
    ``lower_s``, ``backend_s``, and of the backend's records ``cache`` (the
    values seen, joined) and ``saved_s`` (what the cache's entries say they
    saved)."""
    rows = {}
    for r in records:
        row = rows.setdefault((_name(r["program"]), r["thread"]), {
            "program": r["program"], "thread": r["thread"], "trace_s": 0.0, "lower_s": 0.0,
            "backend_s": 0.0, "cache": set(), "saved_s": 0.0})
        row[r["phase"] + "_s"] += r["dur_s"]
        if r["phase"] == "backend":
            row["program"] = r["program"]
            row["cache"].add(r.get("cache") or "off")
            row["saved_s"] += r.get("saved_s") or 0.0
    rows = sorted(rows.values(), reverse=True,
                  key=lambda row: row["trace_s"] + row["lower_s"] + row["backend_s"])
    return [dict(row, cache=",".join(sorted(row["cache"])) or None) for row in rows[:top]]


def union_s(found, intervals):
    """Seconds of the set-up that ``intervals`` cover, each second once."""
    covered = trace_reduce.clip(trace_reduce.merge(list(intervals)), found["lo"], found["hi"])
    return trace_reduce.measure(covered)


def spans_of(records, keep=lambda r: True):
    return [(r["t_mono"], r["t_mono"] + r["dur_s"]) for r in records if keep(r)]


def read(run):
    found = setup(run)
    return None if found is None else union_s(found, spans_of(found["records"]))

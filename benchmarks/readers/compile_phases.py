"""Where set-up's compiling went, from the program's ``compile/<phase>``
records (``perf/sentry.py`` makes one of every duration JAX reports:
``jaxpr_trace``, ``jaxpr_to_mlir``, ``backend_compile``, and on a
persistent-cache hit ``cache_retrieval``, which lies inside
``backend_compile``). Counted: records that ended before the window
opened, so the plain reference's compiles after it are left out, and
what nobody's counter saw before (eager programs) is in. A nested
jit's tracing lies inside its caller's, so phases are summed as the
union of their intervals a thread. ``None`` where the program makes no
such records.

``args``: ``phases``, the phase names to add up, seconds.
"""
from benchmarks.trace import timeline, xplane


def union_s(records) -> float:
    by_thread = {}
    for r in records:
        by_thread.setdefault(r.tid, []).append((r.stamps[0], r.stamps[1]))
    return sum(e - s for spans in by_thread.values()
               for s, e in xplane.merged(spans))


def before_window(obs: dict):
    """``{phase: records}`` of the compiles that ended before the
    window opened, logged once a run and kept in ``obs``; ``None``
    where the program makes no such records."""
    start = obs["spans"]["set-up"][0][0]        # the process's start
    records = timeline.window_records(obs, since=start)
    by_phase = {}
    for r in records or ():
        if r.name.startswith("compile/") \
                and r.stamps[-1] <= obs["setup_end"]:
            by_phase.setdefault(r.name[len("compile/"):], []).append(r)
    for phase, recs in sorted(by_phase.items()):
        causes = {}
        for r in recs:
            causes[r.cause] = causes.get(r.cause, 0.0) \
                + r.stamps[1] - r.stamps[0]
        top = sorted(causes.items(), key=lambda kv: -kv[1])[:4]
        timeline.log("compile before the window: %-16s %8.3f s in %4d "
                     "records; by cause (plain sums) %s" % (
                         phase, union_s(recs), len(recs),
                         ", ".join(f"{c} {s:.3f}" for c, s in top)))
    return by_phase or None


def read(obs: dict, args: dict):
    if "compile_phases" not in obs:
        obs["compile_phases"] = before_window(obs)
    by_phase = obs["compile_phases"]
    if by_phase is None:
        return None
    picked = [r for p in args["phases"] for r in by_phase.get(p, [])]
    return union_s(picked) if picked else None

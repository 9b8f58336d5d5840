"""One timeline: the program's own records beside the device trace.

The program keeps a ring of records that is always on
(``deeplearning4j_tpu/obs/trace.py``: one tuple a step, a loop
iteration, a request, a compile phase; stamps on ``time.perf_counter``).
The profiler's xplane counts nanoseconds from its session's start, the
``profile_start_time`` of its "Task Environment" plane, which is
Unix-epoch nanoseconds. ``obs.trace.clock()`` anchors the one clock to
the other, and this module lays both on the xplane's axis:

    rel_ns(t) = to_epoch_ns(t, anchor) - profile_start_time

The anchor is checked against the trace itself in every traced run
(:func:`check_clock`): each dispatched program on the device's "XLA
Modules" line has to start after the program's ``dispatch`` stamp of
the step that launched it and end before that step's ``sync`` returns.
Over all steps of the tail that leaves an interval of offsets between
the clocks under which the timeline is causal: from the latest
``module_end - sync_end`` (floor) to the earliest ``module_start -
dispatch_start`` (ceiling, the second estimate of the offset, off by
the launch latency alone where the device waits for nothing else).

On the TPU the device planes do NOT sit exactly on
``profile_start_time``: in the serving cells the anchor's offset lay
0.24 ms above that interval in one run and 0.57 ms inside it in the
next, the same program and steps (my chip run 1, PR 24; PERF.md §6).
So the offset used is the anchor's, moved into the causal interval
where it lies outside (the least correction that makes every event
follow its dispatch), and the readers return nothing, and say why,
where that correction exceeds :data:`CORRECT_NS`, where more than
:data:`BROKEN_SHARE` of the events still lie outside their records,
or where the clocks drift. What the interval leaves open (its width,
printed) moves idle time only between a step's ``sync`` and the next
step's ``dispatch``.

A program without the ring (a parent commit) has nothing to read:
:func:`program` returns ``None`` and so does every reader.
"""
import glob
import os
import re
from pathlib import Path

import numpy as np

from benchmarks.trace import xplane

ROOT = Path(__file__).resolve().parents[2]
#: a module event may lie outside its dispatch→sync record by this much
AGREE_NS = 200_000
#: the anchor's offset may need this much correction to be causal
CORRECT_NS = 2_000_000
#: share of module events that may break causality by more than that
BROKEN_SHARE = 0.01
#: drift of the two clocks against each other allowed over the window
DRIFT_NS = 100_000
OUTSIDE = "outside-program"


def log(msg: str) -> None:
    print(msg, flush=True)


def program():
    """The program's record ring, or ``None`` where it has none."""
    try:
        from deeplearning4j_tpu.obs import trace
    except ImportError:
        return None
    if not all(hasattr(trace, f) for f in ("records", "clock", "anchor",
                                           "to_epoch_ns")):
        return None
    return trace


def window_records(obs: dict, since=None):
    """The ring's records from ``since`` (the window's opening without
    it) on, or ``None`` without a ring. Fails (``LookupError``) where
    the ring has overwritten that start."""
    trace = program()
    if trace is None:
        return None
    return trace.records(since=obs["window"][0] if since is None
                         else since)


def phase_bounds(rec, phase: str):
    """``(start, end)`` of one phase of a phased record."""
    i = rec.phases.index(phase)
    return rec.stamps[i], rec.stamps[i + 1]


def feeding_thread(records, top: str):
    """The thread whose records are named ``top``: the ``fit`` caller,
    the gateway's worker."""
    tids = [r.tid for r in records if r.name == top]
    return max(set(tids), key=tids.count) if tids else None


def spans_of(records, tid):
    """``(name, start, end)`` of everything one thread recorded: each
    record whole (``<name>/step`` where it has phases) and each of its
    phases."""
    out = []
    for r in records:
        if r.tid != tid or r.ph != "X":
            continue
        out.append((r.name + "/step" if r.phases else r.name,
                    r.stamps[0], r.stamps[-1]))
        for phase, a, b in zip(r.phases or (), r.stamps, r.stamps[1:]):
            out.append((f"{r.name}/{phase}", a, b))
    return out


def profile_origin_ns(lo_ns: int, hi_ns: int):
    """``profile_start_time`` (epoch ns) of the newest xplane under
    ``.bench_out/trace/`` whose session started between the two epoch
    times (this run's window); ``None`` where there is none."""
    from jax.profiler import ProfileData
    files = glob.glob(str(ROOT / ".bench_out" / "trace" / "*" / "plugins"
                          / "profile" / "*" / "*.xplane.pb"))
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "Task Environment":
                continue
            start = dict(plane.stats).get("profile_start_time")
            if start is not None and lo_ns <= int(start) <= hi_ns:
                return int(start)
    return None


def pair_by_anchor(mods, steps, anchor_ns: float):
    """Module events with the steps that dispatched them: a module
    belongs to the last step dispatched before it started, by the
    anchor's offset with :data:`CORRECT_NS` to spare. The anchor is
    good to a millisecond or so and steps lie tens of milliseconds
    apart, so the pairing does not lean on what it is then used to
    check; under an anchor that is far off the pairs come out wrong
    and the check fails. (Pairing in order from the trace's end does
    not do: the profiler goes on recording for some steps after the
    window closes, by a time that differs from run to run.) Returns
    ``[(module, step), ...]`` and the modules left without a step."""
    starts = np.array([s[0] for s in steps])
    pairs, orphans = [], 0
    for mod in mods:
        at = (mod[0] - anchor_ns + CORRECT_NS) / 1e9
        i = int(np.searchsorted(starts, at, "right")) - 1
        if i < 0:
            orphans += 1
        else:
            pairs.append((mod, steps[i]))
    return pairs, orphans


def check_clock(pairs, anchor_ns: float) -> dict:
    """The anchor's offset held against the trace: ``pairs`` are
    ``((module_start_ns, module_end_ns), (dispatch_s, sync_end_s,
    ...))``, module times from the profile's start, step times on the
    program's clock; ``anchor_ns`` is what the anchor adds to ``step *
    1e9``. ``offset_ns`` is the anchor's moved into the causal
    interval ``[floor, ceiling]``."""
    starts = np.array([m[0] - s[0] * 1e9 for m, s in pairs])
    ends = np.array([m[1] - s[1] * 1e9 for m, s in pairs])
    floor, ceiling = float(ends.max()), float(starts.min())
    offset = float(np.clip(anchor_ns, floor, ceiling)) \
        if floor <= ceiling else (floor + ceiling) / 2
    early = offset - starts             # > 0: started before dispatch
    late = ends - offset                # > 0: ended after the sync
    return {"events": len(pairs),
            "broken": int(np.sum((early > AGREE_NS) | (late > AGREE_NS))),
            "anchor_ns": float(anchor_ns), "offset_ns": offset,
            "floor_ns": floor, "ceiling_ns": ceiling,
            "correction_ns": offset - float(anchor_ns)}


def attribute(gaps, spans, top):
    """Idle seconds by what the feeding thread was doing. Each gap
    ``(start_s, end_s)`` is cut where a span starts or ends, and each
    piece goes to the shortest span covering it (the deepest record or
    phase): a top-level record's own time to ``<name> (self)``, what no
    record covers to ``outside-program``. (``xplane.idle_gaps`` gives a
    whole gap to the span over its middle; here a gap often runs from
    one step's fetch through the next one's staging, so it is cut.)
    Returns ``{label: seconds}``."""
    if not spans:
        return {OUTSIDE: sum(g1 - g0 for g0, g1 in gaps)} if gaps else {}
    names = [s[0] for s in spans]
    a = np.array([s[1] for s in spans])
    b = np.array([s[2] for s in spans])
    length = b - a
    cuts = np.unique(np.concatenate([a, b]))
    pieces = []
    for g0, g1 in gaps:
        inner = cuts[np.searchsorted(cuts, g0, "right"):
                     np.searchsorted(cuts, g1, "left")]
        edges = [g0, *inner, g1]
        pieces += zip(edges, edges[1:])
    total = {}
    for lo in range(0, len(pieces), 4096):      # bound the mask's size
        part = np.array(pieces[lo:lo + 4096])
        mid = part.mean(axis=1)[:, None]
        cover = (a[None, :] <= mid) & (mid <= b[None, :])
        pick = np.where(cover, length[None, :], np.inf).argmin(axis=1)
        for (p0, p1), j, covered in zip(part, pick, cover.any(axis=1)):
            label = OUTSIDE
            if covered:
                label = names[j] + " (self)" if names[j] in top \
                    else names[j]
            total[label] = total.get(label, 0.0) + (p1 - p0)
    return total


def join(obs: dict, args: dict):
    """The traced tail's idle time by program phase, or ``None`` where
    there is no device trace, no ring, or a clock that fails its check.

    ``args``: ``top`` (the feeding thread's top-level record), ``step``
    (the step record that dispatches the device program) and ``module``
    (that program's name on the "XLA Modules" line). Returns ``{"idle":
    {label: seconds}, "idle_s", "gaps", "launches", "steps", "check",
    "drift_ns"}``, times on the program's clock; ``launches`` holds
    ``(step record, module start)`` of every step of the tail. The
    result is kept in ``obs`` so that each metric reads the same one.
    """
    key = ("timeline", args["top"], args["step"], args["module"])
    if key not in obs:
        obs[key] = _join(obs, args)
    return obs[key]


def _join(obs, args):
    trace, reduced = program(), obs.get("trace")
    devs = [d for d in (reduced or {}).get("devices", []) if d["ops"]]
    if trace is None or not devs:
        return None
    w0, w1 = obs["window"]
    tail0 = w1 - obs["trace_window_s"]
    records = trace.records(since=tail0 - 1.0)
    anchor, first = trace.clock(), trace.anchor()
    origin = profile_origin_ns(trace.to_epoch_ns(w0, anchor),
                               trace.to_epoch_ns(w1, anchor))
    if origin is None:
        log("timeline: no xplane with a profile_start_time inside the "
            "window; nothing joined")
        return None
    skew = anchor.epoch_ns - anchor.perf_s * 1e9
    drift_total = skew - (first.epoch_ns - first.perf_s * 1e9)
    drift = drift_total * (w1 - w0) / max(anchor.perf_s - first.perf_s,
                                          1e-9)
    tid = feeding_thread(records, args["top"])
    rx = re.compile(args["module"])
    mods = sorted((s, s + d) for name, s, d in devs[0]["modules"]
                  if rx.search(name))
    steps = sorted(((phase_bounds(r, "dispatch")[0],
                     phase_bounds(r, "sync")[1], r) for r in records
                    if r.name == args["step"] and r.tid == tid),
                   key=lambda s: s[0])
    pairs, orphans = pair_by_anchor(mods, steps, skew - origin)
    if not pairs:
        log(f"timeline: none of {len(mods)} {args['module']} events "
            f"follows one of {len(steps)} {args['step']} records by "
            "the anchor; nothing joined")
        return None
    check = check_clock(pairs, skew - origin)
    check["broken"] += orphans
    offset = check["offset_ns"]
    log("clock check: %d %s events (of %d) paired with %s records; "
        "offset by the anchor %.0f ns; causal from %.0f (latest sync) "
        "to %.0f ns (earliest launch), %.1f us wide; the anchor lies "
        "%.1f us from the earliest launch and is corrected by %.1f us "
        "(limit %.0f us); %d events outside their dispatch->sync "
        "record by more than %.0f us; anchor %.2f us wide; drift over "
        "the window %.1f us (%.1f us over the %.0f s between the "
        "anchors)" % (
            check["events"], args["module"], len(mods), args["step"],
            check["anchor_ns"], check["floor_ns"], check["ceiling_ns"],
            (check["ceiling_ns"] - check["floor_ns"]) / 1e3,
            (check["ceiling_ns"] - check["anchor_ns"]) / 1e3,
            check["correction_ns"] / 1e3, CORRECT_NS / 1e3,
            check["broken"], AGREE_NS / 1e3, anchor.width_s * 1e6,
            drift / 1e3, drift_total / 1e3,
            anchor.perf_s - first.perf_s))
    sound = (abs(check["correction_ns"]) <= CORRECT_NS
             and check["broken"] <= BROKEN_SHARE * len(mods)
             and abs(drift) <= DRIFT_NS)
    if not sound:
        log("timeline: the clock check FAILED; nothing is reported "
            "from the joined timeline")
        return None
    # idle: the complement of the first device's busy intervals in the
    # traced tail, on the program's clock
    lo, hi = tail0 * 1e9 + offset, w1 * 1e9 + offset
    edges = [lo]
    for s, e in xplane.busy(devs[0]):
        if e > lo and s < hi:
            edges += [max(s, lo), min(e, hi)]
    edges.append(hi)
    gaps = [((edges[i] - offset) / 1e9, (edges[i + 1] - offset) / 1e9)
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    idle = attribute(gaps, spans_of(records, tid), {args["top"]})
    idle_s = sum(idle.values())
    for label, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        log("idle by program phase: %-44s %9.6f s %5.1f%%"
            % (label, sec, 100.0 * sec / idle_s if idle_s else 0.0))
    log("idle by program phase: %-44s %9.6f s in %d gaps (traced tail "
        "%.3f s)" % ("all", idle_s, len(gaps), w1 - tail0))
    launches = [(step[2], (mod[0] - offset) / 1e9)
                for mod, step in pairs if tail0 <= step[0] <= w1]
    return {"idle": idle, "idle_s": idle_s, "gaps": gaps,
            "launches": launches, "check": check, "drift_ns": drift,
            "steps": sum((r.counts or {}).get("steps", 1)
                         for r, _ in launches)}


def idle_between(gaps, spans) -> float:
    """Idle seconds inside ``(start, end)`` spans (disjoint ones)."""
    return sum(max(0.0, min(g1, b) - max(g0, a))
               for a, b in spans for g0, g1 in gaps
               if g0 < b and g1 > a)

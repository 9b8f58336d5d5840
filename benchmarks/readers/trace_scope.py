"""Device time by ``dl4j.*`` scope: what part of a program (a layer, a
block's mixer or feed-forward half, a kernel) the device spent a
step's or an admission's time in.

The join is the PROGRAM's, ``deeplearning4j_tpu/obs/devtime.py``
``joined_events``: it reads this run's ``.xplane.pb`` itself (the
reduced form of ``benchmarks/trace/xplane.py`` keeps an op's name
only), gives every "XLA Ops" event the "XLA Modules" event that holds
it, its SELF time (a loop holds its body) and the scope path its
instruction carries in the trace's own metadata or, where the
instruction only moves data, its consumer's. This reader imports it at
read time, as ``trace/timeline.py`` imports the ring, and only sums.
A program without that join (a parent commit) gives ``None``.

The whole table is logged once a traced run, a program name at a time:
scope, self seconds, share of the program's self time, ms a launch
(and the backward ops' part of a training program's row); the rows
sum to the programs' self time. A scope is written as its
path, outermost first, digits as ``*`` in the log (``block_*``), an
element left out where the next one only extends it
(``paged_decode.block_2.mixer/ops.rms_norm``); an op no scope reaches
is ``op:<class>``; one whose program the trace does not tell,
``unjoined``.

``args``: ``module`` (pattern on the program's name on the "XLA
Modules" line), ``kind`` one of

- ``joined_share``: self time of the matching programs' ops that
  joined to a ``dl4j.`` scope over all of it, %;
- ``ms``: self time of their ops whose scope matches ``scope``
  (``unjoined`` and ``op:<class>`` are scopes), in ms, ``per``
  ``program`` (over the matching launches, and over ``obs[steps]``
  where one program makes several steps) or ``per`` ``record`` (over
  the ``step`` records of the traced tail that launched them, the
  programs one record launched summed: a retention admission's chunks
  are ONE admission; ``top`` and ``step`` as ``timeline.join`` takes
  them, and ``None`` where its clock check fails on those records).
"""
import glob
import os
import re
import time
from bisect import bisect_left

from benchmarks.trace import timeline, xplane

#: a launch and the module event it is matched with may lie this far
#: apart (two readers of one file; programs lie 0.5 ms or more apart)
MATCH_NS = 1_000
UNJOINED = "unjoined"


def program():
    """The program's scope join, or ``None`` where it has none."""
    try:
        from deeplearning4j_tpu.obs import devtime
    except ImportError:
        return None
    return devtime if hasattr(devtime, "joined_events") else None


def find_xplane(obs: dict):
    """This run's ``.xplane.pb``: the newest under ``.bench_out/trace``
    written since the window opened, or ``None``."""
    files = glob.glob(str(timeline.ROOT / ".bench_out" / "trace" / "*"
                          / "plugins" / "profile" / "*" / "*.xplane.pb"))
    opened = time.time() - (time.perf_counter() - obs["window"][0])
    files = [f for f in files if os.path.getmtime(f) >= opened]
    return max(files, key=os.path.getmtime) if files else None


def label(event: dict) -> str:
    """One event's scope as the table writes it."""
    path = event["path"]
    if not path:
        return "op:" + xplane.label(event["op"])
    kept = [a for a, b in zip(path, path[1:] + ("",))
            if not b.startswith(a + ".")]
    return "/".join(kept)


def named(scope: str) -> bool:
    """Whether a table row is a ``dl4j.`` scope."""
    return scope != UNJOINED and not scope.startswith("op:")


def reduce(events) -> list:
    """Joined events summed a launch: ``[{"module", "program_id",
    "plane", "start_ns", "scopes": {scope: self_ns}, "backward":
    {scope: self_ns of its backward ops}}, ...]``; the events of one
    program that no "XLA Modules" event holds (a trace's edge) make
    one entry with ``start_ns`` ``None``."""
    launches = {}
    for e in events:
        key = (e["plane"], e["module"], e["program_id"], e["launch_ns"])
        at = launches.get(key)
        if at is None:
            at = launches[key] = {
                "module": e["module"], "program_id": e["program_id"],
                "plane": e["plane"], "start_ns": e["launch_ns"],
                "scopes": {}, "backward": {}}
        name = label(e)
        at["scopes"][name] = at["scopes"].get(name, 0.0) + e["self_ns"]
        if e["backward"]:
            at["backward"][name] = (at["backward"].get(name, 0.0)
                                    + e["self_ns"])
    return list(launches.values())


def log_table(launches) -> None:
    """The whole table, a program name at a time."""
    by_module = {}
    for at in launches:
        by_module.setdefault(at["module"] or UNJOINED, []).append(at)
    for module, mine in sorted(by_module.items()):
        n = sum(1 for at in mine if at["start_ns"] is not None)
        rows, back = {}, {}
        for at in mine:
            for scope, ns in at["scopes"].items():
                row = re.sub(r"\d+", "*", scope)
                rows[row] = rows.get(row, 0.0) + ns
                back[row] = (back.get(row, 0.0)
                             + at["backward"].get(scope, 0.0))
        total = sum(rows.values())
        for row, ns in sorted(rows.items(), key=lambda kv: -kv[1]):
            timeline.log("device time by scope: %-16s %-64s %9.6f s "
                         "%5.1f%% %9.4f ms a launch%s" % (
                             module, row, ns / 1e9,
                             100.0 * ns / total if total else 0.0,
                             ns / 1e6 / max(n, 1),
                             ", %.0f%% of it backward" % (
                                 100.0 * back[row] / ns)
                             if back[row] else ""))
        joined_ns = sum(ns for row, ns in rows.items() if named(row))
        timeline.log("device time by scope: %-16s %-64s %9.6f s in %d "
                     "launches of %d programs, %.2f%% of it under a "
                     "dl4j. scope" % (
                         module, "all (self time)", total / 1e9, n,
                         len({at["program_id"] for at in mine}),
                         100.0 * joined_ns / total if total else 0.0))


def joined(obs: dict):
    """This run's launches with their time by scope (:func:`reduce`),
    or ``None`` without a device trace or without the program's join.
    Kept in ``obs``; the table is logged as it is made."""
    if "scope_join" not in obs:
        obs["scope_join"] = _joined(obs)
    return obs["scope_join"]


def _joined(obs):
    devtime = program()
    if devtime is None or not (obs.get("trace") or {}).get("devices"):
        return None
    path = find_xplane(obs)
    if path is None:
        timeline.log("scope join: no xplane written since the window "
                     "opened; nothing joined")
        return None
    t0 = time.perf_counter()
    events = devtime.joined_events([path])
    launches = reduce(events)
    timeline.log("scope join: %d op events of %s in %d launches, "
                 "joined in %.2f s" % (len(events), path, len(launches),
                                       time.perf_counter() - t0))
    log_table(launches)
    return launches


def by_record(obs: dict, args: dict, launches):
    """The launches grouped by the ``step`` record that dispatched
    them (``timeline.join``'s pairing and clock check), or ``None``.
    Kept in ``obs``, as the pairing is."""
    key = ("scope_records", args["top"], args["step"], args["module"])
    if key not in obs:
        obs[key] = _by_record(obs, args, launches)
    return obs[key]


def _by_record(obs, args, launches):
    paired = timeline.join(obs, {k: args[k]
                                 for k in ("top", "step", "module")})
    if paired is None:
        return None
    offset = paired["check"]["offset_ns"]
    first = min((at["plane"] for at in launches), default=None)
    mine = sorted((at for at in launches if at["plane"] == first),
                  key=lambda at: at["start_ns"])
    starts = [at["start_ns"] for at in mine]
    groups = {}
    for record, launch_s in paired["launches"]:
        want = launch_s * 1e9 + offset
        i = bisect_left(starts, want - MATCH_NS)
        if i < len(starts) and abs(starts[i] - want) <= MATCH_NS:
            groups.setdefault(id(record), (record, []))[1].append(mine[i])
    if groups:
        walls = [timeline.phase_bounds(r, "sync")[1]
                 - timeline.phase_bounds(r, "dispatch")[0]
                 for r, _ in groups.values()]
        timeline.log("device time by scope: %d %s records of the tail "
                     "launched %d %s programs; dispatch start -> sync "
                     "end %.4f ms a record" % (
                         len(groups), args["step"],
                         sum(len(g) for _, g in groups.values()),
                         args["module"], 1e3 * sum(walls) / len(walls)))
    return [g for _, g in groups.values()]


def read(obs: dict, args: dict):
    table = joined(obs)
    if table is None:
        return None
    rx = re.compile(args["module"])
    launches = [at for at in table if rx.search(at["module"])]
    if not launches:
        return None
    if args["kind"] == "joined_share":
        total = sum(ns for at in launches for ns in at["scopes"].values())
        under = sum(ns for at in launches
                    for scope, ns in at["scopes"].items() if named(scope))
        return 100.0 * under / total if total else None
    if args["kind"] != "ms":
        raise ValueError(f"unknown kind {args['kind']!r}")
    launches = [at for at in launches if at["start_ns"] is not None]
    if args["per"] == "record":
        groups = by_record(obs, args, launches)
        if not groups:
            return None
        launches, over = [at for g in groups for at in g], len(groups)
    elif args["per"] == "program":
        over = len(launches) * (obs[args["steps"]] if "steps" in args
                                else 1)
    else:
        raise ValueError(f"unknown per {args['per']!r}")
    scope = re.compile(args["scope"])
    spent = sum(ns for at in launches for name, ns in at["scopes"].items()
                if scope.search(name))
    return spent / 1e6 / over if over else None

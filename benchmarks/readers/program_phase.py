"""Host phases from the program's own records (the always-on ring of
``deeplearning4j_tpu/obs/trace.py``), over the WHOLE window: the ring
and ``obs["window"]`` are on one clock, ``time.perf_counter``. A
program without the ring (a parent commit) gives ``None``.

``args``: ``quantity`` one of

- ``phase_ms_per_step``: time in ``phase`` of the records named
  ``record``, over their ``steps`` counts, ms;
- ``host_gap_ms``: mean, over consecutive ``record`` steps of the
  window with no ``between`` record (an admission's prefill) between
  them, of the time from one step's ``sync`` end to the next step's
  ``dispatch`` start: delivery, the loop's bookkeeping and the feed;
- ``share_of_window``: share of the window the feeding thread spent in
  records named ``record`` whose ``where`` count is above zero, %.
"""
from bisect import bisect_left, bisect_right

from benchmarks.trace import timeline


def read(obs: dict, args: dict):
    records = timeline.window_records(obs)
    if records is None:
        return None
    w0, w1 = obs["window"]
    mine = [r for r in records if r.name == args["record"]
            and r.stamps[0] >= w0 and r.stamps[-1] <= w1]
    if not mine:
        return None
    if args["quantity"] == "phase_ms_per_step":
        spent = sum(b - a for a, b in (
            timeline.phase_bounds(r, args["phase"]) for r in mine))
        return 1e3 * spent / sum(r.counts["steps"] for r in mine)
    if args["quantity"] == "host_gap_ms":
        between = sorted(r.stamps[0] for r in records
                         if r.name == args["between"])
        gaps = []
        for prev, nxt in zip(mine, mine[1:]):
            end = timeline.phase_bounds(prev, "sync")[1]
            start = timeline.phase_bounds(nxt, "dispatch")[0]
            if bisect_left(between, end) == bisect_right(between, start):
                gaps.append(start - end)
        return 1e3 * sum(gaps) / len(gaps) if gaps else None
    if args["quantity"] == "share_of_window":
        spent = sum(r.stamps[-1] - r.stamps[0] for r in mine
                    if r.counts[args["where"]] > 0)
        return 100.0 * spent / (w1 - w0)
    raise ValueError(f"unknown quantity {args['quantity']!r}")

"""Roofline shares of a hybrid state-space decoder's decode step: the
state bytes its Mamba layers must move, the weight bytes a step must
read and the KV pages its attention layers walk, all counted here from
the configuration's shapes (``benchmarks/trace/shapes_ssm.py``), over
what the device trace says the work took and the memory bandwidth in
``benchmarks/trace/peaks.py``.

The program contributes two facts a step of the traced tail: its live
slots (``active``) and the KV pages its attention walked
(``kv_pages``), both on its ``serving.decode_step`` records. Its own
``state_bytes`` count on the same records is a cross-check: it has to
equal ``active`` times the bytes a slot's step carries over as the
shapes give them, and a traced run in which it does not fails here, so
neither side can drift alone.

The recurrence's time is read BY SCOPE (``devtime.joined_events``
through ``readers/trace_scope.py``), not by an op's name: it is the
same work whether a kernel or plain XLA does it.

A program without the counts, the scope or the join (a parent commit)
gives ``None``.

``args``: ``kind`` one of

- ``state``: mean state bytes the recurrences of a step of the tail
  move (``H`` of every Mamba layer, both ways), over the device self
  time a step spent under ``scope``;
- ``step``: what a step carries over (states and tails) plus a step's
  weight bytes plus the walked pages' bytes (``block`` positions a
  page, as the cell's gateway has it), over the mean device time of the
  programs matching ``module``.
"""
from benchmarks.readers import trace_scope
from benchmarks.trace import shapes_ssm, timeline, xplane
from benchmarks.trace.peaks import peaks


def tail_counts(obs: dict):
    """Mean live slots and KV pages of the decode steps recorded in the
    traced tail of the window, or ``None`` where no record counts both
    ``state_bytes`` and ``kv_pages`` above zero."""
    records = timeline.window_records(obs)
    if records is None or "trace_window_s" not in obs:
        return None
    end = obs["window"][1]
    per_slot = shapes_ssm.decode_state_bytes_per_slot(obs["config"])
    rows = []
    for r in records:
        if (r.name != "serving.decode_step" or not r.counts
                or not r.counts.get("state_bytes")
                or not r.counts.get("kv_pages")
                or not end - obs["trace_window_s"] <= r.stamps[0] <= end):
            continue
        need = r.counts["active"] * per_slot
        if r.counts["state_bytes"] != need:
            raise ValueError(
                f"the program counts {r.counts['state_bytes']} state "
                f"bytes for a step of {r.counts['active']} live slots; "
                f"the configuration's shapes give {need}")
        rows.append((r.counts["active"], r.counts["kv_pages"]))
    if not rows:
        return None
    return {"active": sum(a for a, _ in rows) / len(rows),
            "kv_pages": sum(p for _, p in rows) / len(rows)}


def read(obs: dict, args: dict):
    trace = obs.get("trace")
    if not trace or not trace["devices"] or "layer_types" not in obs.get(
            "config", {}):
        return None
    counts = tail_counts(obs)
    if not counts:
        return None
    cfg = obs["config"]
    bandwidth = peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    if args["kind"] == "state":
        ms = trace_scope.read(obs, {"kind": "ms", "per": "program",
                                    "module": args["module"],
                                    "scope": args["scope"]})
        if not ms:
            return None
        moved = counts["active"] * shapes_ssm.decode_h_bytes_per_slot(cfg)
        return 100.0 * moved / (ms / 1e3 * bandwidth)
    if args["kind"] == "step":
        steps = xplane.module_durations(trace, args["module"])
        if not steps:
            return None
        need = (counts["active"]
                * shapes_ssm.decode_state_bytes_per_slot(cfg)
                + shapes_ssm.decode_weight_bytes(cfg)
                + counts["kv_pages"] * args["block"]
                * shapes_ssm.kv_bytes_per_row(cfg))
        return 100.0 * need / (sum(steps) / len(steps) * bandwidth)
    raise ValueError(f"unknown kind {args['kind']!r}")

"""Roofline shares of a retention model's decode step: the state bytes
its steps must move and the weight bytes a step must read, both counted
here from the configuration's shapes
(``benchmarks/trace/shapes_retention.py``), over what the device trace
says the work took and the memory bandwidth in
``benchmarks/trace/peaks.py``.

The program contributes one fact, the number of live slots each step
of the traced tail served (``active`` on its ``serving.decode_step``
records). Its own ``state_bytes`` count on the same records is a
cross-check: it has to equal ``active`` times the bytes a slot's step
moves as the shapes give them, and a traced run in which it does not
fails here, so neither side can drift alone.

A program without the count or the kernel (a parent commit) gives
``None``.

``args``: ``kind`` one of

- ``state``: mean state bytes a step of the tail, over the device
  time a step spent in the ops named ``op`` (all layers' calls);
- ``step``: the same bytes plus a step's weight bytes, over the mean
  device time of the programs matching ``module``.
"""
from benchmarks.trace import shapes_retention, timeline, xplane
from benchmarks.trace.peaks import peaks


def tail_state_bytes(obs: dict):
    """Mean state bytes of the decode steps recorded in the traced
    tail of the window: live slots times a slot's bytes by the
    shapes, or ``None`` where no record counts ``state_bytes``."""
    records = timeline.window_records(obs)
    if records is None or "trace_window_s" not in obs:
        return None
    end = obs["window"][1]
    per_slot = shapes_retention.decode_state_bytes_per_slot(obs["config"])
    moved = []
    for r in records:
        if (r.name != "serving.decode_step" or not r.counts
                or "state_bytes" not in r.counts
                or not end - obs["trace_window_s"] <= r.stamps[0] <= end):
            continue
        need = r.counts["active"] * per_slot
        if r.counts["state_bytes"] != need:
            raise ValueError(
                f"the program counts {r.counts['state_bytes']} state "
                f"bytes for a step of {r.counts['active']} live slots; "
                f"the configuration's shapes give {need}")
        moved.append(need)
    return sum(moved) / len(moved) if moved else None


def read(obs: dict, args: dict):
    trace = obs.get("trace")
    if not trace or not trace["devices"]:
        return None
    moved = tail_state_bytes(obs)
    steps = xplane.module_durations(trace, args["module"])
    if not moved or not steps:
        return None
    bandwidth = peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    if args["kind"] == "state":
        spent = sum(d for dev in trace["devices"]
                    for name, _, d in dev["ops"]
                    if xplane.label(name) == args["op"]) / 1e9
        if not spent:
            return None
        return 100.0 * moved / (spent / len(steps) * bandwidth)
    if args["kind"] == "step":
        need = moved + shapes_retention.decode_weight_bytes(obs["config"])
        return 100.0 * need / (sum(steps) / len(steps) * bandwidth)
    raise ValueError(f"unknown kind {args['kind']!r}")

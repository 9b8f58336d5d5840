"""Device time from the profiler's trace (``benchmarks/trace/xplane``).

``args``: ``quantity`` one of ``idle_share`` (100 less the share of the
traced window in which an operation ran, %) and ``module_ms`` (mean
device time of the dispatched programs whose name matches ``module``,
ms, divided by ``obs[per]`` where one program makes several steps).
"""
from benchmarks.trace import xplane


def read(obs: dict, args: dict):
    trace = obs.get("trace")
    if not trace or not trace["devices"]:
        return None
    if args["quantity"] == "idle_share":
        window = obs["trace_window_s"]
        return 100.0 * (1.0 - xplane.busy_seconds(trace) / window)
    if args["quantity"] == "module_ms":
        durs = xplane.module_durations(trace, args["module"])
        if not durs:
            return None
        per = obs[args["per"]] if "per" in args else 1
        return 1e3 * sum(durs) / len(durs) / per
    raise ValueError(f"unknown quantity {args['quantity']!r}")

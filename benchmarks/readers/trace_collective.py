"""Time the collectives of a several-chip program take on one chip,
from the profiler's trace: the device time of the operations whose
name (instance number stripped) starts with one of ``ops``, on the
first device, a dispatched program matching ``module``, in ms. A trace
without such operations (one chip; a parent that cannot run the cell)
gives ``None``.

``args``: ``ops`` (name prefixes, e.g. ``all-reduce``), ``module``.
"""
import re

from benchmarks.trace import xplane


def read(obs: dict, args: dict):
    trace = obs.get("trace")
    devices = [d for d in (trace or {}).get("devices", []) if d["ops"]]
    if not devices:
        return None
    first = devices[0]
    rx = re.compile(args["module"])
    steps = sum(1 for name, _, _ in first["modules"] if rx.search(name))
    spent = sum(d for name, _, d in first["ops"]
                if xplane.label(name).startswith(tuple(args["ops"])))
    if not steps or not spent:
        return None
    return spent / 1e6 / steps

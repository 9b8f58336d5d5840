"""Reduction of a profiler trace to device metrics.

``load`` turns an ``.xplane.pb`` into a small plain form that JSON can
hold (so a recorded trace can be kept with the tests); every other
function works on that form:

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, dur_ns], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` is the device's "XLA Ops" line (what ran, nested where a loop
holds its body), ``modules`` its "XLA Modules" line (one event a
dispatched program), ``host`` the benchmark's own
``jax.profiler.TraceAnnotation`` spans, on the same clock.
"""
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, host_names) -> dict:
    from jax.profiler import ProfileData
    host_names = set(host_names)
    out = {"devices": [], "host": []}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [[op_name(e.name), e.start_ns,
                                   e.duration_ns] for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events if e.name in host_names)
    return out


def op_name(text: str) -> str:
    """The TPU trace names an op by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``): keep the name."""
    return text.split(" = ", 1)[0].lstrip("%")


def merged(intervals):
    """Union of ``(start, end)`` intervals, as sorted disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy(device: dict):
    """Disjoint intervals in which an operation ran on this device."""
    return merged((s, s + d) for _, s, d in device["ops"] if d > 0)


def busy_seconds(trace: dict) -> float:
    """Seconds an operation ran, averaged over the devices used."""
    per = [sum(e - s for s, e in busy(dev)) / 1e9
           for dev in trace["devices"] if dev["ops"]]
    return sum(per) / len(per) if per else 0.0


def self_times(ops):
    """``[name, self_ns]`` of every op: its duration less what the ops
    nested inside it cover (a loop holds its body's ops)."""
    out = []
    stack = []          # [end, index into out]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0]:
            out[stack[-1][1]][1] -= d
        out.append([name, d])
        stack.append([s + d, len(out) - 1])
    return out


def label(name: str) -> str:
    """An op's name without its instance number: ``fusion.123`` and
    ``fusion.7`` add up under ``fusion``."""
    return re.sub(r"[.\d]+$", "", name) or name


def top_ops(trace: dict, n: int = 10):
    """The device operations that took most time: ``[label, seconds]``,
    self time, summed over instances and devices."""
    total = {}
    for dev in trace["devices"]:
        for name, ns in self_times(dev["ops"]):
            total[label(name)] = total.get(label(name), 0.0) + ns / 1e9
    return sorted(([k, v] for k, v in total.items() if v > 0),
                  key=lambda kv: -kv[1])[:n]


def module_durations(trace: dict, pattern: str):
    """Seconds of every dispatched program whose name matches."""
    rx = re.compile(pattern)
    return [d / 1e9 for dev in trace["devices"]
            for name, _, d in dev["modules"] if rx.search(name)]


def idle_gaps(trace: dict, default: str = "unattributed", n: int = 10):
    """Idle time of the first device by what the host was doing:
    ``[span name, seconds]``. A gap goes to the shortest of the
    benchmark's spans that covers its middle, or to ``default``."""
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        return []
    spans = sorted(trace["host"], key=lambda h: h[2])
    total = {}
    b = busy(devs[0])
    for (_, e0), (s1, _) in zip(b, b[1:]):
        mid = (e0 + s1) / 2
        name = next((nm for nm, s, d in spans if s <= mid <= s + d),
                    default)
        total[name] = total.get(name, 0.0) + (s1 - e0) / 1e9
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:n]

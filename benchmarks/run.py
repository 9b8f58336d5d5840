"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix, metric
or reader is a file of its own, found by the name ``BENCHMARK.json``
gives it (see PERF.md, "Driven by data"); nothing is registered here.
The last line of standard output is the result object. The run fails,
and prints none, when JAX finds no TPU, fewer chips than the cell asks
for, or a device that is not in the table of peaks.
"""
import time

T_START = time.perf_counter()       # set-up counts from here

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


#: start and end of the one call in which the device's runtime starts
DEVICE_START = []


def load_json(path):
    with open(path) as f:
        return json.load(f)


def resolve(cell: str) -> dict:
    """The cell's entry, its workload and configuration files, and the
    metrics it reports, all by name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == cell), None)
    if entry is None:
        raise SystemExit(f"no cell {cell!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])

    def reported(kind):
        return [m for m in bench[kind]
                if cell in m.get("workloads", [cell])]

    return {"cell": cell, "chips": entry["chips"],
            "workload": load_json(BENCH / "workloads" / f"{cell}.json"),
            "config": load_json(ROOT / conf["file"]),
            "end_to_end": reported("end_to_end"),
            "per_layer": reported("per_layer")}


def describe_device(chips: int) -> dict:
    """The device as JAX reports it; a run without the chips it asks
    for, or on a device whose peaks are unknown, fails here."""
    import jax
    Context.mark("jax imported")

    from benchmarks.trace.peaks import peaks
    t0 = time.perf_counter()
    devs = jax.devices()
    DEVICE_START[:] = [t0, time.perf_counter()]
    Context.mark("device runtime started (setup_s leaves this call out)")
    if devs[0].platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chip(s); JAX found "
                         f"{len(devs)}")
    peaks(devs[0].device_kind)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class Context:
    """What a driver gets: the cell's files, the run's arguments, and
    the benchmark's own spans, trace and checks."""

    def __init__(self, spec, seed, seconds, trace=False, trace_dir=None):
        self.config, self.workload = spec["config"], spec["workload"]
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.trace_dir = trace_dir
        self.span_names = set()
        self.trace_window = None

    @staticmethod
    def plugin(kind: str, name: str):
        return importlib.import_module(f"benchmarks.{kind}.{name}")

    @staticmethod
    def log(msg: str) -> None:
        print(msg, flush=True)

    @staticmethod
    def mark(what: str) -> None:
        """Where set-up's time goes: seconds since the process began."""
        print(f"[{time.perf_counter() - T_START:7.2f} s] {what}",
              flush=True)

    def annotate(self, name: str):
        """A span of the benchmark's own in the profiler's trace."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        self.span_names.add(name)
        return jax.profiler.TraceAnnotation(name)

    def start_trace(self, host_spans: bool = True) -> None:
        """Trace the device and, with ``host_spans``, the benchmark's
        own spans. A driver whose calls move large arrays from the host
        turns them off: with the host tracer on at any level, ``fit``'s
        staging of a float32 batch ran 4 to 7 times slower and stopping
        the trace took a minute or more (my chip run 3, PR 23)."""
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1 if host_spans else 0
        jax.profiler.start_trace(str(self.trace_dir),
                                 profiler_options=opts)
        self.trace_window = [time.perf_counter(), None]

    def stop_trace(self) -> None:
        import jax
        self.trace_window[1] = time.perf_counter()
        jax.profiler.stop_trace()

    def check(self, name: str, value, limit) -> dict:
        """One number compared, printed beside its limit."""
        ok = bool(value <= limit)
        self.log(f"check {name}: value={value!r} limit={limit!r} "
                 f"{'ok' if ok else 'NOT CORRECT'}")
        return {"name": name, "value": value, "limit": limit, "ok": ok}

    def compile_report(self) -> dict:
        """The program's compile counters, as set-up left them."""
        from deeplearning4j_tpu import perf
        stats = perf.compile_cache.cache_stats()
        self.log(f"compile cache {stats['dir']}: {stats['bytes']} bytes "
                 f"in {stats['entries']} entries")
        return {"compile_s": perf.sentry.total_compile_time_s(),
                "requests": stats["compile_requests"],
                "hits": stats["persistent_hits"]}

    def memory_peak_bytes(self) -> int:
        """Peak bytes held on the fullest chip, so far: the arrays'
        peak and, beside it, what the runtime reserved for loaded
        programs' temporaries (``peak_bytes_in_use`` alone leaves those
        out: a 268 MB matrix product's left it at 270 MB; my chip run,
        PR 23)."""
        import jax
        stats = [d.memory_stats() or {} for d in jax.devices()]
        self.log(f"memory_stats {stats[0]}")
        return int(max(s.get("peak_bytes_in_use", 0)
                       + s.get("peak_bytes_reserved", 0) for s in stats))


def setup_spans(end: float) -> list:
    """Set-up on the host's clock: from the process's start to the
    window's opening, less the one call in which the device's runtime
    starts. That call runs nothing of the benchmark or the program (the
    program is not yet imported), and it takes 6.0 to 7.2 s on one
    machine and 10.7 to 11.8 s on another, steady on each: a shift of
    13 to 16% of a warm set-up between two sets of the same code (my
    chip runs 16 and 17, PR 23; PERF.md section 2)."""
    if not DEVICE_START:
        return [[T_START, end, 1]]
    return [[T_START, DEVICE_START[0], 1], [DEVICE_START[1], end, 0]]


def read_metrics(entries, obs: dict) -> dict:
    """Each metric through the reader its file names; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in entries:
        spec = load_json(BENCH / "metrics" / f"{entry['name']}.json")
        reader = Context.plugin("readers", spec["reader"])
        value = reader.read(obs, spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             device: dict, trace_dir: Path) -> dict:
    """Drive one cell and reduce what it observed to the result object.
    ``device`` comes from :func:`describe_device`; only the tests pass
    another (they never print a result under a device metric's name)."""
    ctx = Context(spec, seed, seconds, trace, trace_dir)
    driver = ctx.plugin("drivers", spec["workload"]["driver"])
    obs = driver.run(ctx)
    obs.setdefault("spans", {})["set-up"] = setup_spans(obs["setup_end"])
    obs["config"], obs["device"] = spec["config"], device
    device = dict(device, memory_peak_bytes=obs["memory_peak_bytes"])
    result = {"correct": all(c["ok"] for c in obs["checks"]),
              "attempted": obs["attempted"], "failed": obs["failed"]}
    if trace:
        from benchmarks.trace import xplane
        reduced = xplane.load(xplane.find(str(trace_dir)), ctx.span_names)
        obs["trace"] = reduced
        device["busy_s"] = xplane.busy_seconds(reduced)
        device["window_s"] = ctx.trace_window[1] - ctx.trace_window[0]
        obs["trace_window_s"] = device["window_s"]
        result["metrics"] = read_metrics(spec["per_layer"], obs)
        result["breakdown"] = {"device_ops": xplane.top_ops(reduced),
                               "idle_gaps": xplane.idle_gaps(
                                   reduced, obs.get("idle_span",
                                                    "unattributed"))}
    else:
        result["metrics"] = read_metrics(spec["end_to_end"], obs)
    result["device"] = device
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = resolve(args.workload)
    device = describe_device(spec["chips"])
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      device, ROOT / ".bench_out" / "trace" / args.workload)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

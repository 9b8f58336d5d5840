"""Per-op summary of an XProf capture (VERDICT r4 ask #5) — and, since
PR 2, of an ``obs`` span-trace JSONL.

XProf mode parses the ``*.xplane.pb`` a ``jax.profiler.trace`` run
writes (e.g. ``perf_dossier.py --trace DIR``) through the
dependency-free wire parser in ``obs/devtime.py`` (the pinned
``jax.profiler.ProfileData`` does not show an event's metadata stats,
where a TPU keeps an op's program and framework path, and the
tensorboard plugin wheel ships no xplane proto) and prints:

- steps observed and mean device step time (cross-checks the
  wall-clock differencing protocol in ``perf_dossier._timeit``);
- total device time by op CLASS (fusion kinds, custom-call = Pallas
  kernels, convolution/dot = MXU, copies, ...);
- the top-K individual ops by total time with their share.

A DIRECTORY argument resolves to the newest capture session under it
and merges EVERY ``*.xplane.pb`` of that session — one file per host,
so a multi-host capture summarizes the whole fleet instead of
silently dropping all hosts but one. An explicit ``*.xplane.pb`` FILE
argument reads exactly that plane (one host of a fleet capture).

``--comm`` mode reuses the communication observatory's attribution
(``obs/commtime.py``) over the same xplane capture: per-scope
collective device time (scope from each event's ``op_name`` metadata
when no executables are registered), per-kind collective op counts,
total comm share of device time, and the wire-bound scopes — the
offline twin of ``tpu_watch --comm``.

Obs mode reads the Chrome-trace JSONL the telemetry spine writes
(``DL4J_TPU_TRACE=...``, ``deeplearning4j_tpu/obs/trace.py``) — the
host-side step/ETL/sync attribution complementing XProf's device view
— and prints per-span-name totals, counts, and share of the traced
wall time per thread.

    python tools/xprof_summary.py DIR_OR_FILE [--top 10]

A ``*.jsonl``/``*.json`` path (or a dir containing one but no
``*.xplane.pb``) selects obs mode; a ``*.xplane.pb`` path or a
capture dir selects XProf mode.
"""
from __future__ import annotations

import argparse
import re
import sys
from collections import defaultdict
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_NAME_RE = re.compile(r"%?([a-zA-Z0-9_-]+?)(?:\.\d+)? =")
_KIND_RE = re.compile(r"kind=(k\w+)")


def _classify(name: str) -> str:
    m = _NAME_RE.search(name)
    base = m.group(1) if m else name.split(" ")[0].lstrip("%")
    if base == "fusion":
        k = _KIND_RE.search(name)
        return f"fusion:{k.group(1)[1:].lower()}" if k else "fusion"
    # bare post-optimization names ("broadcast_maximum_fusion",
    # "dot.5") — strip the trailing .N the regex above missed
    base = name.split(" ")[0].lstrip("%")
    return base.rsplit(".", 1)[0] if \
        base.rsplit(".", 1)[-1].isdigit() else base


def summarize(trace_path: str, top: int = 10):
    """Per-op device-time table from an XProf capture: an explicit
    ``*.xplane.pb`` file, or a dir whose NEWEST session's planes are
    all merged (multi-host captures keep every host)."""
    from deeplearning4j_tpu.obs import devtime

    paths = devtime.xplane_paths(trace_path)
    steps, per_op, per_class = [], defaultdict(float), \
        defaultdict(float)
    counts = defaultdict(int)
    for p in paths:
        xs = devtime.read_xspace(p)
        steps.extend(devtime.step_durations_ns(xs))
        for ev in devtime.op_events(xs):
            # a TPU names an event by its whole instruction ("text":
            # the fusion's kind is on it) and nests a loop's body
            # inside the loop: self time there; on the CPU's host
            # lines nothing nests and a container is left out
            cls = _classify(ev.get("text", ev["op"]))
            if cls in ("while", "conditional", "call") \
                    and not ev["device_line"]:
                continue        # containers: children counted already
            per_op[ev["op"]] += ev["self_ns"]
            per_class[cls] += ev["self_ns"]
            counts[cls] += 1
    total = sum(per_class.values())
    if not total:
        raise SystemExit(
            f"{trace_path} has no XLA-op execution events — nothing "
            "executed under the trace (or the capture is host-only)")
    out = []
    out.append(f"planes: {len(paths)} file(s) "
               f"({', '.join(Path(p).name for p in paths)})")
    if steps:
        out.append(f"steps: {len(steps)}, mean device step "
                   f"{sum(steps) / max(1, len(steps)) / 1e6:.2f} ms")
    out.append("")
    out.append("| op class | total ms | % | count |")
    out.append("|---|---|---|---|")
    for cls, ns in sorted(per_class.items(), key=lambda kv: -kv[1]):
        if ns / total < 0.005:
            continue
        out.append(f"| {cls} | {ns / 1e6:.2f} | "
                   f"{100 * ns / total:.1f}% | {counts[cls]} |")
    out.append("")
    out.append(f"| top-{top} individual ops | total ms | % |")
    out.append("|---|---|---|")
    for name, ns in sorted(per_op.items(),
                           key=lambda kv: -kv[1])[:top]:
        out.append(f"| `{name[:70]}` | {ns / 1e6:.2f} | "
                   f"{100 * ns / total:.1f}% |")
    return "\n".join(out)


def summarize_comm(trace_path: str, top: int = 10) -> str:
    """Per-scope collective-time table from an XProf capture via the
    comm observatory's attribution. With no registered executables
    the scope join falls back to the events' ``op_name`` metadata —
    sufficient for any capture of ``named_scope``-annotated programs
    (``perf_dossier.py --trace DIR``)."""
    from deeplearning4j_tpu.obs import commtime, devtime

    paths = devtime.xplane_paths(trace_path)
    view = commtime.attribute(paths, maps=None)
    if not view["total_device_ms"]:
        raise SystemExit(
            f"{trace_path} has no XLA-op execution events — nothing "
            "executed under the trace (or the capture is host-only)")
    out = [f"planes: {view['planes']} file(s); total device "
           f"{view['total_device_ms']:.2f} ms, collective "
           f"{view['collective_ms']:.2f} ms "
           f"({100 * view['comm_share']:.1f}%)"]
    if view["estimate_only"]:
        out.append("NOTE: non-TPU capture — collective timings are "
                   "host-side copies, estimate-only")
    if view["by_kind"]:
        out.append("op counts: " + ", ".join(
            f"{c}× {k}" for k, c in view["by_kind"].items()))
    out.append("")
    out.append("| scope | collective ms | share of device | kinds |")
    out.append("|---|---|---|---|")
    ranked = sorted(view["scopes"].items(),
                    key=lambda kv: -kv[1]["collective_ms"])[:top]
    for name, r in ranked:
        kinds = ", ".join(f"{c}× {k}"
                          for k, c in sorted(r["kinds"].items()))
        out.append(f"| {name} | {r['collective_ms']:.3f} | "
                   f"{100 * r['share']:.1f}% | {kinds or '—'} |")
    if view["wire_bound_scopes"]:
        out.append("")
        out.append("wire-bound scopes: "
                   + ", ".join(view["wire_bound_scopes"]))
    return "\n".join(out)


def summarize_obs(path: str, top: int = 10) -> str:
    """Span-name totals from an obs trace JSONL: wall coverage per
    thread, per-name total/count/share — the table the acceptance
    criterion ("spans cover >= 95% of wall time with ETL/step/sync
    attribution") is eyeballed against."""
    import sys as _sys
    _sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from deeplearning4j_tpu.obs import trace as obs_trace

    events = obs_trace.read_trace(path)
    spans = [e for e in events if e.get("ph") == "X"]
    if not spans:
        raise SystemExit(f"{path} contains no complete ('X') spans")
    names = {}
    tid_names = {e["tid"]: e["args"]["name"] for e in events
                 if e.get("ph") == "M"
                 and e.get("name") == "thread_name"}
    by_tid = defaultdict(list)
    for e in spans:
        by_tid[e["tid"]].append(e)
        k = e["name"]
        tot, cnt = names.get(k, (0.0, 0))
        names[k] = (tot + e.get("dur", 0.0), cnt + 1)
    wall = (max(e["ts"] + e.get("dur", 0.0) for e in spans)
            - min(e["ts"] for e in spans))
    out = [f"events: {len(spans)} spans over {wall / 1e3:.1f} ms "
           f"wall, {len(by_tid)} thread(s)"]
    for tid, evs in sorted(by_tid.items()):
        t_wall = (max(e["ts"] + e.get("dur", 0.0) for e in evs)
                  - min(e["ts"] for e in evs)) or 1.0
        # top-level spans only (not contained in any other span of the
        # thread) so nested phases don't double-count coverage
        evs_sorted = sorted(evs, key=lambda e: (e["ts"],
                                                -e.get("dur", 0.0)))
        covered = end = 0.0
        for e in evs_sorted:
            s, d = e["ts"], e.get("dur", 0.0)
            if s + d <= end:
                continue
            covered += (s + d) - max(s, end)
            end = s + d
        out.append(f"thread {tid_names.get(tid, tid)}: "
                   f"{100 * covered / t_wall:.1f}% of "
                   f"{t_wall / 1e3:.1f} ms covered by spans")
    out.append("")
    out.append(f"| span | total ms | % | count |")
    out.append("|---|---|---|---|")
    for k, (tot, cnt) in sorted(names.items(),
                                key=lambda kv: -kv[1][0])[:top]:
        out.append(f"| {k} | {tot / 1e3:.2f} | "
                   f"{100 * tot / wall:.1f}% | {cnt} |")
    return "\n".join(out)


def _is_obs_trace(path: Path) -> bool:
    if path.is_file():
        return path.suffix in (".jsonl", ".json")
    return (not any(path.rglob("*.xplane.pb"))
            and any(path.rglob("*.jsonl")))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir",
                    help="XProf capture dir, or an obs trace JSONL")
    ap.add_argument("--top", type=int, default=10)
    ap.add_argument("--comm", action="store_true",
                    help="per-scope COLLECTIVE time view of an xplane "
                         "capture (obs/commtime.py attribution)")
    args = ap.parse_args()
    p = Path(args.trace_dir)
    if args.comm:
        print(summarize_comm(args.trace_dir, args.top))
    elif _is_obs_trace(p):
        if p.is_dir():
            p = sorted(p.rglob("*.jsonl"),
                       key=lambda q: q.stat().st_mtime)[-1]
        print(summarize_obs(str(p), args.top))
    else:
        print(summarize(args.trace_dir, args.top))


if __name__ == "__main__":
    main()

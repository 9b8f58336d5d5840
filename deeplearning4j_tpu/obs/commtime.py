"""Communication observatory — per-collective wire-byte and
interconnect-time attribution across every parallelism mode.

The devtime observatory (PR 9) answers "which LAYER is the device
computing in"; nothing answered "which PHASE is the interconnect
moving bytes for". ROADMAP item 4 (encoded-gradient collectives) is
blocked on exactly that measurement — "nothing measures wire bytes" —
and `tools/collective_volume.py` only projected volume statically for
three hand-written configs. This module is the comm sibling of
:mod:`~deeplearning4j_tpu.obs.devtime` (ARCHITECTURE.md §19):

1. **Static wire ledger.** :func:`collective_records` walks one
   optimized-HLO module (the collective walker factored out of
   ``tools/collective_volume.py``, which now delegates here) and
   yields one record per collective op: kind, result tensor bytes,
   ring-model wire bytes (sized by the op's PARSED replica groups,
   not a global device count), replica groups, and the ``dl4j.*``
   scope joined through the same ``metadata``/call-graph inheritance
   devtime uses (:func:`~deeplearning4j_tpu.obs.devtime
   .hlo_scope_map`). :func:`wire_ledger` aggregates records across
   any set of sentry-registered executables — so EVERY jitted
   program (DP, ZeRO scatter/gather, gather-overlap, composed
   DP×TP/SP/PP/EP, the serving fleet paths) gets a per-scope wire
   account, not just the hand-picked configs.

2. **Runtime attribution.** :func:`attribute` rides devtime's xplane
   capture pipeline: per-scope device time spent inside collective
   ops (``all-reduce``/``reduce-scatter``/``all-gather``/
   ``collective-permute``/``all-to-all``; async ``-start`` events
   carry the transfer, ``-done`` sync points are excluded), joined
   with the static ledger into an interconnect roofline — measured
   wire GB/s over the device's ``ici_gbs`` peak
   (``environment.DEVICE_PEAKS``). Off-TPU captures
   (CPU/gloo) are labeled ``estimate_only``: thunk timings are host
   copies, not ICI transfers, so only the LEDGER numbers are load-
   bearing there. ``devtime.gap_report`` entries carry the same axis
   (``gap.comm_ms``; ``bound == "wire"`` when collectives dominate).

3. **Live plane.** :func:`capture` / the env-gated
   :class:`Observatory` (``DL4J_TPU_COMMTIME``) publish
   ``dl4j_tpu_comm_*`` gauges through the standing registry — which
   the PR 7 fleet snapshots embed verbatim, so ``/fleet`` re-labels
   per-scope wire bytes and link utilization with host/mesh-epoch:
   per-host link health is routable state. ``tpu_watch --comm``
   renders the table + WIRE_BOUND alarm; ``bench.py`` carries the
   ``comm`` section (the PR 5 ZeRO byte gates, measured); the
   dossier carries the ``comm_observatory`` row.

Off path: with ``DL4J_TPU_COMMTIME`` unset the fit-loop hooks
(:func:`step_started`/:func:`step_ended`) are one module-global
``is None`` branch — zero profiler sessions, zero captures, zero
publishes, counter-fenced by ``tests/test_commtime.py``.
"""
from __future__ import annotations

import re
import shutil
import tempfile
import threading
from typing import Any, Dict, Iterable, List, Optional, Tuple

from deeplearning4j_tpu.obs import devtime as _devtime
from deeplearning4j_tpu.obs import metrics as _metrics
from deeplearning4j_tpu.obs import trace as _trace
from deeplearning4j_tpu.obs.devtime import (COLLECTIVE_KINDS,
                                            WIRE_BOUND_SHARE,
                                            collective_kind)

_TRUTHY = {"1", "true", "on", "yes"}

_lock = threading.Lock()
_counters = {"captures": 0, "sessions": 0}

#: the env-gated cadence monitor (None = off: the one branch every
#: un-observed step pays in the fit loops)
_MONITOR: Optional["Observatory"] = None

#: the last completed comm capture (tools / dossier tail)
_last_report: Optional[Dict[str, Any]] = None


def captures() -> int:
    """Completed comm capture-and-attribute pipelines since reset —
    with ``DL4J_TPU_COMMTIME`` unset and no explicit :func:`capture`
    call this stays 0 (the off-path fence)."""
    return _counters["captures"]


def profiler_sessions() -> int:
    """``jax.profiler`` sessions started by this module since reset."""
    return _counters["sessions"]


def reset_counters() -> None:
    global _last_report
    with _lock:
        _counters["captures"] = 0
        _counters["sessions"] = 0
    _last_report = None


def last_report() -> Optional[Dict[str, Any]]:
    return _last_report


# ---------------------------------------------------------------------------
# static wire ledger: the HLO collective walker (factored out of
# tools/collective_volume.py — that tool now delegates here)
# ---------------------------------------------------------------------------

# HLO line shape: `%name = <shape-or-tuple> <opcode>(...), ...` — the
# result may be a TUPLE (XLA fuses many gradients into one all-reduce)
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s*(\(?[^(=]*?(?:\([^)]*\))?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_LHS_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4,
                "u32": 4, "s64": 8, "u64": 8, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16}

_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{(\{[0-9,]+\}"
                                r"(?:,\{[0-9,]+\})*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
    r"(?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")


def _tensor_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(",") if dims else []:
        n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_replica_groups(line: str):
    """Replica groups of one HLO collective line, as a frozenset of
    frozensets of device ids — handles both the literal
    ``{{0,2},{1,3}}`` and the iota ``[G,S]<=[dims]T(perm)`` forms.
    None for the empty/absent form (all devices one group)."""
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        return frozenset(
            frozenset(int(d) for d in g.split(","))
            for g in m.group(1)[1:-1].split("},{"))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        import numpy as np
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            arr = arr.transpose([int(p) for p in m.group(4).split(",")])
        arr = arr.reshape(g, s)
        return frozenset(frozenset(int(d) for d in row) for row in arr)
    return None


def parse_source_target_pairs(line: str
                              ) -> Optional[List[Tuple[int, int]]]:
    """``source_target_pairs`` of a collective-permute line."""
    m = _PAIRS_RE.search(line)
    if not m or not m.group(1):
        return None
    return [tuple(int(x) for x in p.split(","))
            for p in m.group(1)[1:-1].split("},{")]


def ring_wire_bytes(kind: str, tensor_bytes: float,
                    group_size: int) -> float:
    """Per-device ring-algorithm wire bytes for one collective whose
    HLO RESULT is ``tensor_bytes`` over a ``group_size`` ring:

    - all-reduce: ``2·N·(n−1)/n`` (reduce-scatter + all-gather)
    - all-gather: ``N/n·(n−1)`` (result is the FULL gathered tensor;
      each device sends its shard to n−1 peers)
    - reduce-scatter: ``N·(n−1)`` (result is the shard)
    - collective-permute: ``N`` (one neighbor hop)
    - all-to-all: ``N·(n−1)/n``
    """
    n = int(group_size)
    if n <= 1:
        return 0.0      # a one-device group moves nothing
    nb = float(tensor_bytes)
    return {"all-reduce": 2.0 * nb * (n - 1) / n,
            "all-gather": nb / n * (n - 1),
            "reduce-scatter": nb * (n - 1),
            "collective-permute": nb,
            "all-to-all": nb * (n - 1) / n}[kind]


def collective_records(hlo_text: str, n_devices: Optional[int] = None,
                       uniform_ring: Optional[int] = None
                       ) -> List[Dict[str, Any]]:
    """Walk one optimized-HLO module → one ledger record per
    collective op (async ``-done`` halves excluded): ``{"module",
    "op", "kind", "tensor_bytes", "wire_bytes", "group_size",
    "replica_groups", "source_target_pairs", "scope", "backward",
    "in_while", "trips"}``.

    The ring model is sized by the op's PARSED replica groups (the
    largest group — a DP×TP program's tensor-axis all-reduce rings
    over 2 devices, not 8), falling back to ``n_devices`` when the
    groups are absent/empty. ``uniform_ring`` overrides the group
    size for every op — the legacy ``collective_volume.py`` knob its
    analytic rows are pinned to. ``scope`` is the innermost ``dl4j.``
    scope via :func:`devtime.hlo_scope_map` (metadata + call-graph
    inheritance), or None for an anonymous collective. Collectives
    inside a ``while`` body (the ring-attention fori_loop) execute
    once per trip; the ring's trip count is its group size."""
    smap = _devtime.hlo_scope_map(hlo_text)
    ops = smap["ops"]
    out: List[Dict[str, Any]] = []
    for line in hlo_text.splitlines():
        head = line.split("metadata=")[0]
        m = _COLLECTIVE_LINE_RE.search(head)
        if not m or "-done" in head:
            continue
        shapes, kind = m.groups()
        nb = sum(_tensor_bytes(d, dims)
                 for d, dims in _SHAPE_RE.findall(shapes))
        groups = parse_replica_groups(line)
        if uniform_ring:
            g = int(uniform_ring)
        elif groups:
            g = max(len(grp) for grp in groups)
        elif n_devices:
            g = int(n_devices)
        else:
            g = 2
        lhs = _LHS_RE.match(line)
        op = lhs.group(1) if lhs else ""
        info = ops.get(op)
        scope = info["scope"] if info and info["scope"] else None
        in_while = "/while/" in line
        trips = g if in_while else 1
        out.append({
            "module": smap["module"], "op": op, "kind": kind,
            "tensor_bytes": nb,
            "wire_bytes": ring_wire_bytes(kind, nb, g) * trips,
            "group_size": g, "replica_groups": groups,
            "source_target_pairs": parse_source_target_pairs(line),
            "scope": scope,
            "backward": bool(info and info["backward"]),
            "in_while": in_while, "trips": trips})
    return out


def wire_ledger(executables: Iterable[Any] = (), *,
                n_devices: Optional[int] = None) -> Dict[str, Any]:
    """The static half of the observatory: aggregate
    :func:`collective_records` across ``executables`` (anything with
    ``.as_text()`` — ``devtime.sentry_executables`` output, or
    ``.lower().compile()`` results) into per-scope and per-kind wire
    accounts, assuming each program executes once per step. Anonymous
    collectives (no ``dl4j.`` scope on the op or any caller)
    aggregate under ``op:<kind>`` keys — lint rule 11 keeps the
    in-repo collective emitters scoped so those stay empty."""
    ex = [e for e in executables if e is not None]
    if n_devices is None:
        import jax
        n_devices = jax.device_count()
    records: List[Dict[str, Any]] = []
    for c in ex:
        try:
            text = c.as_text()
        except Exception:
            continue
        records.extend(collective_records(text, n_devices=n_devices))
    by_scope: Dict[str, Dict[str, Any]] = {}
    by_kind: Dict[str, Dict[str, Any]] = {}
    total = 0.0
    for r in records:
        key = r["scope"] if r["scope"] else f"op:{r['kind']}"
        s = by_scope.setdefault(key, {"wire_bytes": 0.0,
                                      "tensor_bytes": 0.0,
                                      "kinds": {}})
        s["wire_bytes"] += r["wire_bytes"]
        s["tensor_bytes"] += r["tensor_bytes"] * r["trips"]
        s["kinds"][r["kind"]] = s["kinds"].get(r["kind"], 0) + 1
        k = by_kind.setdefault(r["kind"], {"count": 0,
                                           "wire_bytes": 0.0})
        k["count"] += 1
        k["wire_bytes"] += r["wire_bytes"]
        total += r["wire_bytes"]
    return {"n_devices": int(n_devices), "programs": len(ex),
            "records": records, "by_scope": by_scope,
            "by_kind": by_kind, "wire_bytes": total}


# ---------------------------------------------------------------------------
# runtime attribution + interconnect roofline
# ---------------------------------------------------------------------------

def peak_ici_from_env() -> float:
    """Interconnect roofline peak in bytes/s per link direction of
    the attached device (``environment.device_peaks``: the
    ``device_kind`` table, or the explicit ``DL4J_TPU_PEAK_ICI_GBS``
    override; an unknown device is an error)."""
    from deeplearning4j_tpu import environment
    return environment.device_peaks("ici_gbs")["ici_gbs"] * 1e9


def _estimate_only() -> bool:
    """CPU/gloo captures time host-side thunk copies, not ICI
    transfers — their utilization numbers are wiring-validation only
    (the ledger bytes remain exact)."""
    try:
        import jax
        return jax.devices()[0].platform != "tpu"
    except Exception:
        return True


def comm_view(att: Dict[str, Any],
              ledger: Optional[Dict[str, Any]] = None,
              peak_ici: Optional[float] = None) -> Dict[str, Any]:
    """Project a ``devtime.attribute`` capture onto the comm axis and
    join the static ``ledger``: per-scope collective seconds, share of
    device time, wire bytes/step, and achieved-vs-peak interconnect
    utilization (``wire GB/s / DL4J_TPU_PEAK_ICI_GBS``)."""
    peak = peak_ici or peak_ici_from_env()
    total_ms = att["total_device_ms"]
    execs = [m.get("executions", 1) for m in att["modules"].values()]
    steps = att["device_steps"] or (max(execs) if execs else 1) or 1
    by_scope = (ledger or {}).get("by_scope", {})
    scopes: Dict[str, Dict[str, Any]] = {}
    by_kind: Dict[str, int] = {}
    total_comm = 0.0
    for name, e in att["scopes"].items():
        kinds: Dict[str, int] = {}
        for k, c in e.get("kinds", {}).items():
            base = collective_kind(k)
            if base:
                kinds[base] = kinds.get(base, 0) + c
        comm_ms = e.get("comm_ms", 0.0)
        led = by_scope.get(name)
        if comm_ms <= 0 and not kinds and led is None:
            continue
        total_comm += comm_ms
        for k, c in kinds.items():
            by_kind[k] = by_kind.get(k, 0) + c
        rec: Dict[str, Any] = {
            "collective_ms": comm_ms,
            "device_ms": e["device_ms"],
            "share": round(comm_ms / total_ms, 6) if total_ms else 0.0,
            "wire_bound": bool(
                e["device_ms"] > 0
                and comm_ms > WIRE_BOUND_SHARE * e["device_ms"]),
            "kinds": kinds,
        }
        if led is not None:
            rec["wire_bytes_per_step"] = led["wire_bytes"]
            rec["tensor_bytes_per_step"] = led["tensor_bytes"]
            if comm_ms > 0:
                gbs = (led["wire_bytes"] * steps
                       / (comm_ms / 1e3)) / 1e9
                rec["achieved_gbs"] = round(gbs, 6)
                rec["link_utilization"] = round(gbs * 1e9 / peak, 6)
        scopes[name] = rec
    return {
        "total_device_ms": total_ms,
        "collective_ms": round(total_comm, 6),
        "comm_share": round(total_comm / total_ms, 6)
        if total_ms else 0.0,
        "device_steps": att["device_steps"],
        "planes": att["planes"],
        "peak_ici_gbs": peak / 1e9,
        "estimate_only": _estimate_only(),
        "by_kind": dict(sorted(by_kind.items(), key=lambda kv: -kv[1])),
        "wire_bytes_per_step": (ledger or {}).get("wire_bytes"),
        "wire_bound_scopes": sorted(
            n for n, r in scopes.items() if r["wire_bound"]),
        "scopes": scopes,
    }


def attribute(paths: Iterable[str],
              maps: Optional[Dict[str, Any]] = None,
              ledger: Optional[Dict[str, Any]] = None,
              peak_ici: Optional[float] = None) -> Dict[str, Any]:
    """Runtime half over raw xplane ``paths``: one
    ``devtime.attribute`` pass (scope join through the same maps),
    projected onto the comm axis via :func:`comm_view`. With
    ``maps=None`` the scope join falls back to each event's
    ``op_name`` metadata (``tools/xprof_summary.py --comm``)."""
    return comm_view(_devtime.attribute(paths, maps=maps),
                     ledger=ledger, peak_ici=peak_ici)


def _publish(view: Dict[str, Any], top: int = 12) -> None:
    """Export the last comm capture as ``dl4j_tpu_comm_*`` gauges.
    Scope-label cardinality bounded by ``top``; stale labels dropped
    so the scrape always shows ONE capture's ranking. The fleet
    snapshot embeds the registry exposition verbatim, so these ride
    into ``/fleet`` with host labels for free."""
    for fam in (_metrics.COMM_SCOPE_WIRE_BYTES,
                _metrics.COMM_SCOPE_SECONDS,
                _metrics.COMM_SCOPE_SHARE,
                _metrics.COMM_SCOPE_LINK_UTILIZATION,
                _metrics.COMM_OP_COUNT,
                _metrics.COMM_WIRE_BOUND_SCOPES):
        with fam._lock:
            fam._children.clear()
    ranked = sorted(view["scopes"].items(),
                    key=lambda kv: -kv[1]["collective_ms"])[:top]
    for name, r in ranked:
        _metrics.COMM_SCOPE_SECONDS.labels(scope=name).set(
            r["collective_ms"] / 1e3)
        _metrics.COMM_SCOPE_SHARE.labels(scope=name).set(r["share"])
        if "wire_bytes_per_step" in r:
            _metrics.COMM_SCOPE_WIRE_BYTES.labels(scope=name).set(
                r["wire_bytes_per_step"])
        if "link_utilization" in r:
            _metrics.COMM_SCOPE_LINK_UTILIZATION.labels(
                scope=name).set(r["link_utilization"])
    for kind, count in view["by_kind"].items():
        _metrics.COMM_OP_COUNT.labels(kind=kind).set(count)
    for name in view["wire_bound_scopes"]:
        _metrics.COMM_WIRE_BOUND_SCOPES.labels(scope=name).set(1)


# ---------------------------------------------------------------------------
# capture pipelines: on demand + cadence
# ---------------------------------------------------------------------------

def capture(run, *, executables: Iterable[Any] = (),
            label: str = "on_demand", top: int = 12,
            keep_dir: Optional[str] = None) -> Dict[str, Any]:
    """The on-demand pipeline: run ``run()`` under a
    ``jax.profiler.trace`` window, build the static wire ledger from
    ``executables``, attribute the collective device time, publish the
    ``dl4j_tpu_comm_*`` gauges, and return ``{"comm": ...,
    "ledger": ...}``. ``keep_dir`` preserves the raw xplane session
    for ``tools/xprof_summary.py --comm``."""
    import jax

    ex = [e for e in executables if e is not None]
    d = keep_dir or tempfile.mkdtemp(prefix="dl4j_commtime_")
    t0 = _trace.now()
    with _lock:
        _counters["sessions"] += 1
    try:
        with jax.profiler.trace(d):
            run()
    except Exception:
        if keep_dir is None:
            shutil.rmtree(d, ignore_errors=True)
        raise
    try:
        led = wire_ledger(ex)
        view = attribute(_devtime.xplane_paths(d),
                         maps=_devtime.executable_maps(ex),
                         ledger=led)
    finally:
        if keep_dir is None:
            shutil.rmtree(d, ignore_errors=True)
    wall = _trace.now() - t0
    with _lock:
        _counters["captures"] += 1
    _metrics.COMM_CAPTURES.inc()
    _metrics.COMM_CAPTURE_SECONDS.inc(wall)
    _publish(view, top=top)
    global _last_report
    _last_report = {"label": label, "capture_wall_s": round(wall, 6),
                    "comm": view,
                    "ledger": {"wire_bytes": led["wire_bytes"],
                               "by_kind": led["by_kind"],
                               "programs": led["programs"]}}
    if _trace.enabled():
        _trace.instant("commtime/capture",
                       {"label": label, "wall_s": round(wall, 4)})
    return _last_report


class Observatory:
    """Cadence-gated comm capture windows inside the fit loops —
    instantiated from ``DL4J_TPU_COMMTIME``, never on the default
    path. Shares the process profiler politely: if another session
    owns it (devtime's window, the dossier's ``--trace``), the window
    is skipped, never breaking the step."""

    def __init__(self, every: int = 100, steps: int = 3,
                 top: int = 12):
        self.every = max(1, int(every))
        self.steps = max(1, int(steps))
        self.top = int(top)
        self._dir: Optional[str] = None
        self._steps_in = 0
        self._t0 = 0.0

    def capturing(self) -> bool:
        return self._dir is not None

    def due(self, iteration: int) -> bool:
        return iteration % self.every == 0

    def on_step_start(self, iteration: int) -> None:
        if self._dir is not None or not self.due(iteration):
            return
        import jax
        d = tempfile.mkdtemp(prefix="dl4j_commtime_")
        try:
            jax.profiler.start_trace(d)
        except Exception:
            shutil.rmtree(d, ignore_errors=True)
            return
        with _lock:
            _counters["sessions"] += 1
        self._dir = d
        self._steps_in = 0
        self._t0 = _trace.now()

    def on_step_end(self, *step_fns) -> None:
        if self._dir is None:
            return
        self._steps_in += 1
        if self._steps_in < self.steps:
            return
        import jax
        d, self._dir = self._dir, None
        try:
            jax.profiler.stop_trace()
        except Exception:
            shutil.rmtree(d, ignore_errors=True)
            return
        try:
            ex = _devtime.sentry_executables(
                *[f for f in step_fns if f is not None])
            led = wire_ledger(ex)
            view = attribute(_devtime.xplane_paths(d),
                             maps=_devtime.executable_maps(ex),
                             ledger=led)
        except FileNotFoundError:
            shutil.rmtree(d, ignore_errors=True)
            return
        finally:
            shutil.rmtree(d, ignore_errors=True)
        wall = _trace.now() - self._t0
        with _lock:
            _counters["captures"] += 1
        _metrics.COMM_CAPTURES.inc()
        _metrics.COMM_CAPTURE_SECONDS.inc(wall)
        _publish(view, top=self.top)
        global _last_report
        _last_report = {"label": "cadence",
                        "capture_wall_s": round(wall, 6),
                        "comm": view,
                        "ledger": {"wire_bytes": led["wire_bytes"],
                                   "by_kind": led["by_kind"],
                                   "programs": led["programs"]}}


def configure(every: int = 100, steps: int = 3,
              top: int = 12) -> Observatory:
    """Install the cadence monitor programmatically (tests/tools)."""
    global _MONITOR
    _MONITOR = Observatory(every=every, steps=steps, top=top)
    return _MONITOR


def disable() -> None:
    global _MONITOR
    if _MONITOR is not None and _MONITOR.capturing():
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        if _MONITOR._dir:
            shutil.rmtree(_MONITOR._dir, ignore_errors=True)
    _MONITOR = None


def configure_from_env() -> Optional[Observatory]:
    """Install the monitor from ``DL4J_TPU_COMMTIME`` (called by
    ``environment.apply_startup_flags``; the unset path never reaches
    here)."""
    from deeplearning4j_tpu import environment
    raw = str(environment.get_flag("DL4J_TPU_COMMTIME") or "").strip()
    if raw.lower() not in _TRUTHY:
        return None
    return configure(
        every=int(environment.get_flag("DL4J_TPU_COMMTIME_EVERY")),
        steps=int(environment.get_flag("DL4J_TPU_COMMTIME_STEPS")))


# -- fit-loop hooks (the counter-fenced off path) ---------------------------

def step_started(iteration: int) -> None:
    """Called by the fit loops next to ``devtime.step_started``. Off
    path (``DL4J_TPU_COMMTIME`` unset): one module-global ``is None``
    branch — zero profiler sessions, zero allocations."""
    m = _MONITOR
    if m is None:
        return
    m.on_step_start(iteration)


def step_ended(*step_fns) -> None:
    """Called by the fit loops after the step's blocking sync, passing
    the step's ``sentry.jit`` entry points so the ledger can read
    their compiled HLO. Same one-branch off path."""
    m = _MONITOR
    if m is None:
        return
    m.on_step_end(*step_fns)


# ---------------------------------------------------------------------------
# bench probes
# ---------------------------------------------------------------------------

def measure_capture_overhead(step_seconds: Optional[float] = None,
                             iters: int = 20000) -> Dict[str, Any]:
    """The off-path half of the bench ``comm`` section: the two
    fit-loop hook branches every un-observed step pays, and the
    counter fence — synthetic probe state restored."""
    global _MONITOR
    saved, _MONITOR = _MONITOR, None
    c0 = dict(_counters)
    try:
        t0 = _trace.now()
        for i in range(iters):
            step_started(i)
            step_ended(None)
        off = (_trace.now() - t0) / iters
    finally:
        _MONITOR = saved
        with _lock:
            _counters.update(c0)
    out: Dict[str, Any] = {
        "off_path_cost_us": round(off * 1e6, 4),
        "monitor_enabled": _MONITOR is not None,
        "captures": captures(),
        "profiler_sessions": profiler_sessions(),
    }
    if step_seconds:
        out["step_ms"] = round(step_seconds * 1e3, 3)
        out["off_path_pct_of_step"] = round(
            100.0 * off / step_seconds, 5)
    lr = _last_report
    if lr is not None:
        out["last_capture"] = {"label": lr["label"],
                               "wall_s": lr["capture_wall_s"],
                               "comm_share": lr["comm"]["comm_share"]}
    return out


def comm_report(n_devices: int = 8, hidden: int = 256,
                features: int = 64, classes: int = 16
                ) -> Dict[str, Any]:
    """The ``comm`` section of ``bench.py`` / the dossier
    ``comm_observatory`` row: the ZeRO sharded-update step's wire
    ledger on the live device set, gated against the PR 5 HLO byte
    model — reduce-scatter result bytes ≈ grad_bytes/N under the
    ``zero.reduce_scatter`` scope, all-gather result bytes ≈
    param_bytes under ``zero.all_gather``. Plus the off-path fence
    numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = int(n_devices)
    if len(jax.devices()) < n or n < 2:
        return {"skipped": True,
                "reason": f"needs {n} devices, have {len(jax.devices())}"}
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.parallel import ParallelWrapper

    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(upd.Adam(learning_rate=1e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(features)).build())
    net = MultiLayerNetwork(conf).init()
    w = ParallelWrapper(net, workers=n, sharded_update=True)
    w._prepare()
    dshard = NamedSharding(w.mesh, P("data"))
    b = 8 * n
    x = jax.device_put(jnp.zeros((b, features), jnp.float32), dshard)
    y = jax.device_put(jnp.zeros((b, classes), jnp.float32), dshard)
    args = (net.params, w._dp_state, net.state, x, y,
            jax.random.PRNGKey(0))
    compiled = w._step.lower(*args).compile()
    led = wire_ledger([compiled], n_devices=n)
    p_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                  for p in jax.tree_util.tree_leaves(net.params))
    peak = peak_ici_from_env()
    rs = led["by_scope"].get("zero.reduce_scatter",
                             {"tensor_bytes": 0.0, "wire_bytes": 0.0})
    ag = led["by_scope"].get("zero.all_gather",
                             {"tensor_bytes": 0.0, "wire_bytes": 0.0})
    return {
        "n_devices": n,
        "platform": jax.devices()[0].platform,
        "model": f"mlp {features}-{hidden}-{hidden}-{classes} adam "
                 "(ZeRO sharded update)",
        "param_bytes": p_bytes,
        "grad_bytes": p_bytes,     # f32 grads mirror f32 params
        "scopes": {k: {"tensor_bytes": v["tensor_bytes"],
                       "wire_bytes": v["wire_bytes"],
                       "kinds": v["kinds"]}
                   for k, v in sorted(led["by_scope"].items())},
        "wire_bytes_per_step": led["wire_bytes"],
        "t_ici_ms": round(led["wire_bytes"] / peak * 1e3, 4),
        "peak_ici_gbs": peak / 1e9,
        # the PR 5 HLO gates, through the ledger's scope join
        "gates": {
            "reduce_scatter_tensor_over_grad_shard": round(
                rs["tensor_bytes"] / (p_bytes / n), 4)
            if p_bytes else None,
            "all_gather_tensor_over_params": round(
                ag["tensor_bytes"] / p_bytes, 4) if p_bytes else None,
        },
        "off_path": measure_capture_overhead(iters=2000),
    }


__all__ = ["COLLECTIVE_KINDS", "collective_kind", "collective_records",
           "wire_ledger", "ring_wire_bytes", "parse_replica_groups",
           "parse_source_target_pairs", "peak_ici_from_env",
           "comm_view", "attribute", "capture", "Observatory",
           "configure", "configure_from_env", "disable",
           "step_started", "step_ended", "captures",
           "profiler_sessions", "reset_counters", "last_report",
           "measure_capture_overhead", "comm_report",
           "WIRE_BOUND_SHARE"]

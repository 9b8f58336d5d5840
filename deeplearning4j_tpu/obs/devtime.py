"""Device-time observatory — per-layer *device* attribution + roofline.

The PR 2/4/7 spine measures host wall-clock: `obs.record_step` can say
a step took 46 ms, but on an asynchronously-dispatched backend it
cannot say which LAYER the device spent those milliseconds in — the
dispatch returns before the device runs, and XLA fuses the program
into op soup whose names (``fusion.7``, ``dot.5``) carry no model
structure. ROADMAP item "Pallas only where XLA has a gap" is blocked
on exactly that attribution: the cuDNN-primitives shape of the win
(PAPERS.md: arxiv 1410.0759) is a SMALL library of tuned kernels
chosen from measured hot spots, so the hot spots must first be
*named*. This module is the naming instrument:

1. **Scopes.** :func:`scope` wraps ``jax.named_scope`` with a
   recognizable ``dl4j.`` prefix. The fit forwards annotate every
   layer (``nn/multilayer.py``/``nn/graph.py`` ``_forward``), the
   hand-rolled zoo transformer annotates its blocks (``zoo/gpt.py``),
   the serving scheduler its paged decode blocks, and the ZeRO layout
   its collective phases (``parallel/zero.py``). ``named_scope`` is
   trace-time only — zero bytes and zero branches in the compiled
   step; jax carries the scope into the backward program as
   ``transpose(jvp(dl4j.<scope>))`` so gradients attribute too.

2. **Capture.** :func:`capture` (on demand) or the env-gated
   :class:`Observatory` (cadence, ``DL4J_TPU_DEVTIME``) runs a short
   ``jax.profiler.trace`` window around real steps and parses the
   resulting ``*.xplane.pb`` with a dependency-free protobuf
   wire-format reader (:func:`read_xspace` — ``jax.profiler
   .ProfileData`` does not exist on the pinned jaxlib, and the
   tensorboard plugin's proto module is absent from the wheel).
   XLA-op execution events carry ``hlo_op``/``hlo_module`` stats and
   picosecond durations — the device's own account of where time
   went; ``tools/xprof_summary.py`` reads captures through the same
   parser.

3. **Attribution.** The post-optimization HLO of the executed
   programs (``Compiled.as_text()`` — the retrace sentry keeps its
   AOT executables, :func:`sentry_executables`) maps each timed op
   name to its ``metadata={op_name="...dl4j.<scope>..."}`` scope;
   per-op FLOP/byte estimates parsed from the HLO shapes give each
   scope an achieved-vs-roofline utilization (:func:`roofline`,
   peaks by ``device_kind`` from ``environment.DEVICE_PEAKS``),
   and ``Compiled.cost_analysis()`` program totals provide the
   per-module cross-check (the ``modules`` section: XLA's own
   FLOPs/bytes against measured device time, independent of the
   shape-regex estimates).

4. **Gap report.** :func:`gap_report` ranks scopes by device-time
   share with utilization, fusion count, and a ``pallas_candidate``
   flag — the structured answer to "which kernel should the Pallas
   library fill next". It lands in ``tools/perf_dossier.py``
   (``hot_path_gaps``), ``bench.py`` (``devtime``), the
   ``dl4j_tpu_devtime_*`` metric families, and the ``tpu_watch``
   devtime view. Every entry carries exactly :data:`GAP_KEYS` —
   ``tools/lint_instrumentation.py`` rule 8 keeps the keys OPS.md and
   tpu_watch reference resolvable against that tuple.

Off path: with ``DL4J_TPU_DEVTIME`` unset the fit-loop hooks
(:func:`step_started`/:func:`step_ended`) are one module-global
``is None`` branch — zero profiler sessions, zero captures, counter-
fenced by ``tests/test_devtime.py`` (the PR 2 contract).
"""
from __future__ import annotations

import math
import os
import re
import shutil
import struct
import tempfile
import threading
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Tuple

from deeplearning4j_tpu.obs import metrics as _metrics
from deeplearning4j_tpu.obs import trace as _trace

_TRUTHY = {"1", "true", "on", "yes"}

#: every scope emitted through :func:`scope` carries this prefix, so
#: attribution can find the innermost model scope anywhere in an
#: ``op_name`` path (``jit(f)/transpose(jvp(dl4j.layer_0.Dense))/...``)
SCOPE_PREFIX = "dl4j."

_SCOPE_RE = re.compile(r"dl4j\.([\w.:\-]+)")

_lock = threading.Lock()
_counters = {"captures": 0, "sessions": 0}

#: the env-gated cadence monitor (None = off: the one branch every
#: un-observed step pays in the fit loops)
_MONITOR: Optional["Observatory"] = None

#: the last completed capture's gap report (tools / obs.report tail)
_last_report: Optional[Dict[str, Any]] = None


def captures() -> int:
    """Completed capture-and-attribute pipelines since reset — with
    ``DL4J_TPU_DEVTIME`` unset and no explicit :func:`capture` call
    this stays 0 (the off-path fence)."""
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
# scope annotation (trace-time only — nothing survives into the step)
# ---------------------------------------------------------------------------

def scope(name: str):
    """``with devtime.scope("layer_0.DenseLayer"): ...`` around the
    layer math AS TRACED: the compiled program's ops carry the scope
    in their HLO metadata, the compiled step itself is byte-identical
    (metadata never feeds codegen). Use anywhere a device-time total
    should have a model-level name."""
    import jax
    return jax.named_scope(SCOPE_PREFIX + str(name))


# ---------------------------------------------------------------------------
# xplane.pb reader — protobuf wire format, no proto deps
# ---------------------------------------------------------------------------
# Field numbers from tsl/profiler/protobuf/xplane.proto (stable):
#   XSpace.planes=1; XPlane{id=1,name=2,lines=3,event_metadata=4(map),
#   stat_metadata=5(map),stats=6}; XLine{id=1,name=2,timestamp_ns=3,
#   events=4,duration_ps=9,display_name=11}; XEvent{metadata_id=1,
#   offset_ps=2,duration_ps=3,stats=4,timestamp_ns=7};
#   XStat{metadata_id=1,double=2,uint64=3,int64=4,str=5,bytes=6,ref=7};
#   XEventMetadata{id=1,name=2,display_name=4};
#   XStatMetadata{id=1,name=2}; map entry{key=1,value=2}.

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """Yield ``(field_no, wire_type, value)`` over one message body.
    Length-delimited values come back as the raw bytes slice."""
    i, end = 0, len(buf)
    while i < end:
        tag, i = _varint(buf, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:                       # group wire types never appear here
            raise ValueError(f"unsupported wire type {wt} in xplane.pb")
        yield fno, wt, v


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for fno, _wt, v in _fields(buf):
        if fno == 1:
            key = v
        elif fno == 2:
            val = v
    return key, val


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    mid, val = 0, None
    for fno, wt, v in _fields(buf):
        if fno == 1:
            mid = v
        elif fno == 2:
            val = struct.unpack("<d", v)[0]
        elif fno in (3, 4):
            val = v
        elif fno == 5:
            val = v.decode("utf-8", "replace")
        elif fno == 6:
            val = v
        elif fno == 7:              # ref into stat_metadata names
            val = stat_names.get(v, str(v))
    return stat_names.get(mid, str(mid)), val


def read_xspace(path) -> Dict[str, Any]:
    """Parse one ``*.xplane.pb`` into plain dicts::

        {"planes": [{"name", "lines": [{"name", "timestamp_ns",
                     "events": [{"name", "dur_ps", "offset_ps",
                                 "stats": {...}}]}]}]}

    Event names and ref-valued stats are resolved through the plane's
    metadata tables."""
    buf = Path(path).read_bytes()
    planes = []
    for fno, _wt, pbuf in _fields(buf):
        if fno != 1:
            continue
        name = ""
        line_bufs: List[bytes] = []
        ev_names: Dict[int, str] = {}
        stat_names: Dict[int, str] = {}
        for pf, _pw, pv in _fields(pbuf):
            if pf == 2:
                name = pv.decode("utf-8", "replace")
            elif pf == 3:
                line_bufs.append(pv)
            elif pf == 4:
                k, v = _map_entry(pv)
                em_name = ""
                for ef, _ew, evv in _fields(v):
                    if ef == 2:
                        em_name = evv.decode("utf-8", "replace")
                ev_names[k] = em_name
            elif pf == 5:
                k, v = _map_entry(pv)
                sm_name = ""
                for sf, _sw, svv in _fields(v):
                    if sf == 2:
                        sm_name = svv.decode("utf-8", "replace")
                stat_names[k] = sm_name
        lines = []
        for lbuf in line_bufs:
            lname, ts_ns = "", 0
            events = []
            for lf, _lw, lv in _fields(lbuf):
                if lf == 2:
                    lname = lv.decode("utf-8", "replace")
                elif lf == 3:
                    ts_ns = lv
                elif lf == 11 and not lname:
                    lname = lv.decode("utf-8", "replace")
                elif lf == 4:
                    mid = off_ps = dur_ps = 0
                    stats: Dict[str, Any] = {}
                    for ef, _ew, ev in _fields(lv):
                        if ef == 1:
                            mid = ev
                        elif ef == 2:
                            off_ps = ev
                        elif ef == 3:
                            dur_ps = ev
                        elif ef == 4:
                            k, v = _stat(ev, stat_names)
                            stats[k] = v
                    events.append({"name": ev_names.get(mid, str(mid)),
                                   "offset_ps": off_ps,
                                   "dur_ps": dur_ps, "stats": stats})
            lines.append({"name": lname, "timestamp_ns": ts_ns,
                          "events": events})
        planes.append({"name": name, "lines": lines})
    return {"planes": planes}


def xplane_paths(path) -> List[str]:
    """Resolve a capture argument to the xplane file set: an explicit
    ``*.xplane.pb`` file is read alone; a directory resolves to EVERY
    plane file of the NEWEST capture session under it (one session dir
    holds one ``<host>.xplane.pb`` per host — merging them is what
    keeps a multi-host capture from silently dropping hosts)."""
    p = Path(path)
    if p.is_file():
        return [str(p)]
    planes = list(p.rglob("*.xplane.pb"))
    if not planes:
        raise FileNotFoundError(f"no *.xplane.pb under {path}")
    by_session: Dict[Path, List[Path]] = {}
    for q in planes:
        by_session.setdefault(q.parent, []).append(q)
    newest = max(by_session,
                 key=lambda d: max(q.stat().st_mtime
                                   for q in by_session[d]))
    return [str(q) for q in sorted(by_session[newest])]


def op_events(xspace: Dict[str, Any]) -> List[Dict[str, Any]]:
    """XLA-op *execution* events from one parsed xplane: device planes
    contribute their "XLA Ops" lines; the CPU thunk executor (this
    jaxlib's XLA:CPU) reports per-op events on host lines whose stats
    carry ``hlo_op``/``hlo_module``. Returns
    ``[{"op", "module", "dur_ns", "plane"}, ...]``."""
    out = []
    for plane in xspace["planes"]:
        device = "/device:" in plane["name"]
        for line in plane["lines"]:
            dev_line = device and line["name"] in ("XLA Ops",
                                                   "XLA Modules")
            if dev_line and line["name"] == "XLA Modules":
                continue            # per-op granularity only
            for e in line["events"]:
                mod = e["stats"].get("hlo_module")
                if not (dev_line or mod is not None):
                    continue
                op = e["stats"].get("hlo_op") or e["name"]
                if not e["dur_ps"]:
                    continue
                rec = {"op": str(op), "module": str(mod or ""),
                       "dur_ns": e["dur_ps"] / 1e3,
                       "plane": plane["name"]}
                # TPU device planes stamp the framework op path on the
                # event itself ("tf_op") — a scope source that needs
                # no compiled-HLO join at all
                tf_op = e["stats"].get("tf_op")
                if tf_op:
                    rec["op_name"] = str(tf_op)
                out.append(rec)
    return out


def step_durations_ns(xspace: Dict[str, Any]) -> List[float]:
    """Device "Steps" line durations (TPU captures; absent on CPU)."""
    out = []
    for plane in xspace["planes"]:
        if "/device:" not in plane["name"]:
            continue
        for line in plane["lines"]:
            if line["name"] == "Steps":
                out.extend(e["dur_ps"] / 1e3 for e in line["events"])
    return out


# ---------------------------------------------------------------------------
# HLO scope map + per-op cost estimates
# ---------------------------------------------------------------------------

_HLO_MODULE_RE = re.compile(r"^HloModule (\S+?)[,\s]", re.M)
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')
_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([0-9,]*)\]")
_KIND_RE = re.compile(r"^(?:\([^=]*?\)|\S+(?:\{[^}]*\})?)\s+"
                      r"([\w\-]+)\(")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
                "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(dtype: str, dims: str) -> Tuple[int, int]:
    elems = 1
    for d in dims.split(","):
        if d:
            elems *= int(d)
    return elems, elems * _DTYPE_BYTES.get(dtype, 4)


def _op_cost(kind: str, rhs: str,
             shapes: List[Tuple[str, str]]) -> Tuple[float, float]:
    """(flops, bytes) estimate for one optimized-HLO op line: exact
    2·M·N·K math for dots, kernel-volume math for convolutions, one
    flop per output element for everything else; bytes are the sum of
    every shape on the line (result + operands — the traffic an ideal
    cache-less execution moves). Estimates, labeled as such — they
    rank roofline gaps, they are not a simulator."""
    if not shapes:
        return 0.0, 0.0
    bytes_ = float(sum(_shape_bytes(dt, dm)[1] for dt, dm in shapes))
    out_elems = _shape_bytes(*shapes[0])[0]
    flops = float(out_elems)
    if kind == "dot" and len(shapes) >= 2:
        m = _LHS_CONTRACT_RE.search(rhs)
        lhs_dims = [int(x) for x in
                    (m.group(1).split(",") if m and m.group(1) else [])]
        lhs_shape = [int(x) for x in shapes[1][1].split(",") if x]
        k = 1
        for d in lhs_dims:
            if d < len(lhs_shape):
                k *= lhs_shape[d]
        flops = 2.0 * out_elems * k
    elif kind == "convolution" and len(shapes) >= 3:
        kern_elems = _shape_bytes(*shapes[2])[0]
        out_ch = 1
        m = _DIM_LABELS_RE.search(rhs)
        if m and "o" in m.group(2):
            kern_dims = [int(x) for x in shapes[2][1].split(",") if x]
            oi = m.group(2).index("o")
            if oi < len(kern_dims):
                out_ch = kern_dims[oi]
        flops = 2.0 * out_elems * kern_elems / max(1, out_ch)
    return flops, bytes_


_CALLEE_RE = re.compile(
    r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _line_shapes(kind: str, rhs: str,
                 shape_of: Dict[str, Tuple[str, str]]
                 ) -> List[Tuple[str, str]]:
    """Result shape(s) then operand shapes of one HLO op line. The
    installed jax prints operands as bare ``%names`` (no inline
    shapes), so operand shapes come from ``shape_of`` — the result
    shapes of the instructions defined above (HLO is printed in
    definition order). A line that does carry inline operand shapes
    is read as printed."""
    printed = _SHAPE_RE.findall(rhs)
    start = rhs.find(kind + "(")
    if start < 0:
        return printed
    result = _SHAPE_RE.findall(rhs[:start])
    if len(printed) > len(result):      # operand shapes are on the line
        return printed
    args_at = start + len(kind) + 1
    end = rhs.find(")", args_at)
    args = rhs[args_at:end if end >= 0 else len(rhs)]
    return result + [shape_of[n] for n in _OPERAND_RE.findall(args)
                     if n in shape_of]


def hlo_scope_map(hlo_text: str) -> Dict[str, Any]:
    """Map one executable's post-optimization HLO to attribution data:
    ``{"module": name, "ops": {op_name: {"scope", "backward", "kind",
    "flops", "bytes"}}}``. ``scope`` is the INNERMOST ``dl4j.`` scope
    on the op's ``metadata op_name`` path; ops with no metadata of
    their own (while-loop bookkeeping, region bodies — XLA:CPU's
    scatter loops are made of these) INHERIT the scope of the op that
    calls their computation, so a conv-backward scatter's thousands of
    body iterations attribute to the conv layer, not to noise. None
    when no caller on the chain is annotated (optimizer update,
    loss, ...)."""
    m = _HLO_MODULE_RE.search(hlo_text)
    module = m.group(1) if m else ""
    ops: Dict[str, Dict[str, Any]] = {}
    shape_of: Dict[str, Tuple[str, str]] = {}   # op -> result shape
    comp_of: Dict[str, str] = {}       # op -> enclosing computation
    caller_of: Dict[str, str] = {}     # computation -> calling op
    current_comp = ""
    for raw in hlo_text.splitlines():
        line = raw.strip()
        # computation header: `%name (params...) -> result {`
        if line.endswith("{") and ") -> " in line and " = " not in line:
            head = line.split(" ", 1)[0]
            if head == "ENTRY":
                head = line.split(" ", 2)[1]
            current_comp = head.lstrip("%")
            continue
        om = _HLO_OP_RE.match(line)
        if om is None:
            continue
        op, rhs = om.group(1), om.group(2)
        km = _KIND_RE.match(rhs)
        if km:
            kind = km.group(1)
        else:
            head = rhs.split("(")[0].split()
            kind = head[-1] if head else ""
        # this jax prints operands as bare `%names`: remember every
        # instruction's result shape so _line_shapes can look them up
        first = _SHAPE_RE.search(rhs)
        if first and not rhs.startswith("("):
            shape_of[op] = first.groups()
        if not kind or kind == "parameter":
            continue
        for callee in _CALLEE_RE.findall(rhs):
            caller_of.setdefault(callee, op)
        nm = _OP_NAME_RE.search(rhs)
        scope_ = None
        backward = False
        if nm:
            hits = _SCOPE_RE.findall(nm.group(1))
            scope_ = hits[-1] if hits else None
            backward = "transpose(" in nm.group(1)
        shapes = _line_shapes(kind, rhs, shape_of)
        flops, bytes_ = _op_cost(kind, rhs, shapes)
        comp_of[op] = current_comp
        ops[op] = {"scope": scope_, "backward": backward,
                   "kind": kind, "flops": flops, "bytes": bytes_,
                   "has_meta": nm is not None}
    # scope inheritance: un-annotated ops take their calling op's
    # resolved scope (bounded walk — call graphs are shallow)
    def resolve(op: str, depth: int = 0) -> Tuple[Optional[str], bool]:
        info = ops.get(op)
        if info is None or depth > 8:
            return None, False
        if info["scope"] is not None:
            return info["scope"], info["backward"]
        caller = caller_of.get(comp_of.get(op, ""))
        if caller is None or caller == op:
            return None, info["backward"]
        sc, bwd = resolve(caller, depth + 1)
        return sc, (info["backward"] or bwd) if sc is not None \
            else info["backward"]

    for op, info in ops.items():
        if info["scope"] is None:
            sc, bwd = resolve(op)
            info["scope"], info["backward"] = sc, bwd
        info.pop("has_meta", None)
    return {"module": module, "ops": ops}


def sentry_executables(*fns) -> List[Any]:
    """The AOT ``Compiled`` executables a set of ``sentry.jit`` entry
    points keeps after warmup — the zero-recompile source of HLO text
    and ``cost_analysis()`` for attribution. Non-sentried / un-warmed
    arguments contribute nothing (attribution then falls back to
    op-class scopes)."""
    out = []
    for fn in fns:
        aot = getattr(fn, "_aot", None)
        if isinstance(aot, dict):
            out.extend(aot.values())
    return out


def executable_maps(executables: Iterable[Any]) -> Dict[str, Any]:
    """Scope maps keyed by HLO module name, plus merged
    ``cost_analysis()`` program totals per module."""
    maps: Dict[str, Any] = {}
    for ex in executables or ():
        try:
            text = ex.as_text()
        except Exception:
            continue
        sm = hlo_scope_map(text)
        try:
            ca = ex.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            sm["program_flops"] = float(ca.get("flops", 0.0))
            sm["program_bytes"] = float(ca.get("bytes accessed", 0.0))
        except Exception:
            sm["program_flops"] = sm["program_bytes"] = 0.0
        maps[sm["module"]] = sm
    return maps


# ---------------------------------------------------------------------------
# roofline
# ---------------------------------------------------------------------------

def peaks_from_env() -> Tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) of the attached device
    (``environment.device_peaks``: the ``device_kind`` table, or the
    explicit ``DL4J_TPU_PEAK_TFLOPS`` / ``DL4J_TPU_PEAK_HBM_GBS``
    overrides). A device the table does not know is an error. Reports
    carry the peaks used."""
    from deeplearning4j_tpu import environment
    pk = environment.device_peaks("tflops", "hbm_gbs")
    return pk["tflops"] * 1e12, pk["hbm_gbs"] * 1e9


def roofline(flops: float, bytes_: float, seconds: float,
             peak_flops: float, peak_bytes_per_s: float
             ) -> Dict[str, Any]:
    """Achieved-vs-roofline utilization for one measured region: which
    resource bounds it (arithmetic intensity vs the ridge point) and
    how close the measured rate comes to that resource's peak.
    ``utilization`` is the binding-resource fraction — a 0.9 means
    "this region already runs at 90% of what the roofline allows; a
    custom kernel buys little", a 0.1 names a gap."""
    if seconds <= 0 or peak_flops <= 0 or peak_bytes_per_s <= 0:
        return {"achieved_tflops": 0.0, "achieved_gbs": 0.0,
                "compute_utilization": 0.0, "memory_utilization": 0.0,
                "utilization": 0.0, "bound": "unknown"}
    achieved_fs = flops / seconds
    achieved_bs = bytes_ / seconds
    cu = achieved_fs / peak_flops
    mu = achieved_bs / peak_bytes_per_s
    ridge = peak_flops / peak_bytes_per_s        # flops per byte
    intensity = flops / bytes_ if bytes_ > 0 else math.inf
    bound = "compute" if intensity >= ridge else "memory"
    return {"achieved_tflops": round(achieved_fs / 1e12, 6),
            "achieved_gbs": round(achieved_bs / 1e9, 6),
            "compute_utilization": round(cu, 6),
            "memory_utilization": round(mu, 6),
            "utilization": round(cu if bound == "compute" else mu, 6),
            "bound": bound}


# ---------------------------------------------------------------------------
# attribution + gap report
# ---------------------------------------------------------------------------

_CLASS_NAME_RE = re.compile(r"^([a-zA-Z0-9_\-]+?)(?:\.\d+)?$")

#: control-flow containers whose children report their own time —
#: counting both would double-book every loop body (the
#: ``xprof_summary`` skip list, shared rationale)
_CONTAINER_KINDS = {"while", "conditional", "call", "async-start",
                    "async-done", "async-update"}


def _op_class(op: str) -> str:
    m = _CLASS_NAME_RE.match(op)
    return m.group(1) if m else op


#: the five HLO collective opcodes — the comm axis of the gap report
#: and the event filter of ``obs/commtime.py`` (which layers the wire
#: ledger + interconnect roofline on top of this classification)
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")

_COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?(?:\.\d+)?$")


#: the installed jax names an HLO instruction after the PRIMITIVE that
#: made it (``reduce_scatter.31``, ``psum.7``), not after its opcode —
#: an event name is all an offline capture (no executable) has
_PRIMITIVE_KIND = {"psum": "all-reduce", "pmax": "all-reduce",
                   "pmin": "all-reduce", "all_gather": "all-gather",
                   "reduce_scatter": "reduce-scatter",
                   "ppermute": "collective-permute",
                   "all_to_all": "all-to-all"}
_PRIMITIVE_RE = re.compile(
    r"^(" + "|".join(_PRIMITIVE_KIND) + r")(?:-start)?(?:\.\d+)?$")


def collective_kind(op_or_kind: str) -> Optional[str]:
    """Base collective kind of an HLO op name/opcode, or None. The
    async ``-start`` form classifies (its device event carries the
    transfer duration); ``-done`` does not (a sync point — counting
    both would double-book every async collective)."""
    m = _COLLECTIVE_RE.match(op_or_kind)
    if m:
        return m.group(1)
    m = _PRIMITIVE_RE.match(op_or_kind)
    return _PRIMITIVE_KIND[m.group(1)] if m else None


def attribute(paths: Iterable[str],
              maps: Optional[Dict[str, Any]] = None,
              peaks: Optional[Tuple[float, float]] = None
              ) -> Dict[str, Any]:
    """Join timed op events from ``paths`` (xplane files — every host
    of one session) with the executables' scope maps into per-scope
    device-time totals. Ops outside every annotated region aggregate
    under ``op:<class>`` scopes (the xprof class view), so the report
    always accounts for 100% of measured device time."""
    maps = maps or {}
    peak_f, peak_b = peaks or peaks_from_env()
    scopes: Dict[str, Dict[str, Any]] = {}
    module_ns: Dict[str, float] = {}
    module_op_count: Dict[Tuple[str, str], int] = {}
    total_ns = 0.0
    attributed_ns = 0.0
    steps: List[float] = []
    n_planes = 0
    for p in paths:
        xs = read_xspace(p)
        n_planes += len(xs["planes"])
        steps.extend(step_durations_ns(xs))
        for ev in op_events(xs):
            mod_map = maps.get(ev["module"])
            if mod_map is None and ev["module"]:
                # module-name fingerprint suffixes: accept a UNIQUE
                # prefix match, never a blind any-module scan —
                # default HLO names (fusion.1, broadcast.4) collide
                # across programs and would book one program's time
                # to another's scope
                cands = [m for k, m in maps.items()
                         if k and (ev["module"].startswith(k)
                                   or k.startswith(ev["module"]))]
                if len(cands) == 1:
                    mod_map = cands[0]
            info = mod_map["ops"].get(ev["op"]) \
                if mod_map is not None else None
            kind_ = info["kind"] if info else _op_class(ev["op"])
            if kind_ in _CONTAINER_KINDS:
                continue            # children report their own time
            sc = info["scope"] if info and info["scope"] else None
            if sc is None and "op_name" in ev:
                hits = _SCOPE_RE.findall(ev["op_name"])
                sc = hits[-1] if hits else None
            # unattributed ops bucket by class; collectives by their
            # HLO kind whatever the instruction was named after
            key = sc if sc is not None else (
                f"op:{collective_kind(ev['op']) or _op_class(ev['op'])}")
            e = scopes.get(key)
            if e is None:
                e = scopes[key] = {
                    "device_ns": 0.0, "ops": 0, "fusions": 0,
                    "backward_ns": 0.0, "custom_call_ns": 0.0,
                    "collective_ns": 0.0,
                    "flops": 0.0, "bytes": 0.0, "kinds": {}}
            dur = ev["dur_ns"]
            total_ns += dur
            if mod_map is not None:
                module_ns[mod_map["module"]] = \
                    module_ns.get(mod_map["module"], 0.0) + dur
                mk = (mod_map["module"], ev["op"])
                module_op_count[mk] = module_op_count.get(mk, 0) + 1
            e["device_ns"] += dur
            e["ops"] += 1
            kind = info["kind"] if info else _op_class(ev["op"])
            e["kinds"][kind] = e["kinds"].get(kind, 0) + 1
            if "fusion" in kind or "fusion" in ev["op"]:
                e["fusions"] += 1
            if "custom-call" in kind or "custom-call" in ev["op"]:
                e["custom_call_ns"] += dur
            if collective_kind(kind) or collective_kind(ev["op"]):
                e["collective_ns"] += dur
            if info is not None:
                e["flops"] += info["flops"]
                e["bytes"] += info["bytes"]
                if info["backward"]:
                    e["backward_ns"] += dur
            if sc is not None:
                attributed_ns += dur
    out_scopes: Dict[str, Dict[str, Any]] = {}
    for key, e in scopes.items():
        sec = e["device_ns"] / 1e9
        rec: Dict[str, Any] = {
            "device_ms": round(e["device_ns"] / 1e6, 6),
            "share": round(e["device_ns"] / total_ns, 6)
            if total_ns else 0.0,
            "ops": e["ops"], "fusions": e["fusions"],
            "backward_ms": round(e["backward_ns"] / 1e6, 6),
            "custom_call_ms": round(e["custom_call_ns"] / 1e6, 6),
            "comm_ms": round(e["collective_ns"] / 1e6, 6),
            "flops": e["flops"], "bytes": e["bytes"],
            "kinds": dict(sorted(e["kinds"].items(),
                                 key=lambda kv: -kv[1])),
        }
        if e["flops"] or e["bytes"]:
            rec["roofline"] = roofline(e["flops"], e["bytes"], sec,
                                       peak_f, peak_b)
        out_scopes[key] = rec
    # program-level cross-check: XLA's OWN cost_analysis() totals per
    # executed module against its measured device time — the roofline
    # number that does not depend on the regex shape estimates.
    # Executions per module = the MIN occurrence count over its
    # mapped non-container ops in the window: every top-level op runs
    # exactly once per execution (count == executions), loop-body ops
    # run more — min is robust to loop overcount and only
    # underestimates for conditional arms, which merely makes the
    # per-execution roofline conservative.
    modules: Dict[str, Dict[str, Any]] = {}
    for mod, ns in module_ns.items():
        mm = maps.get(mod)
        if mm is None:
            continue
        counts = [c for (m, op), c in module_op_count.items()
                  if m == mod and op in mm["ops"]
                  and mm["ops"][op]["kind"] not in _CONTAINER_KINDS]
        execs = min(counts) if counts else 1
        rec: Dict[str, Any] = {
            "device_ms": round(ns / 1e6, 6),
            "executions": max(1, execs),
            "program_flops": mm.get("program_flops", 0.0),
            "program_bytes": mm.get("program_bytes", 0.0),
        }
        if rec["program_flops"] or rec["program_bytes"]:
            rec["roofline"] = roofline(
                rec["program_flops"] * rec["executions"],
                rec["program_bytes"] * rec["executions"],
                ns / 1e9, peak_f, peak_b)
        modules[mod] = rec
    return {
        "total_device_ms": round(total_ns / 1e6, 6),
        "attributed_ms": round(attributed_ns / 1e6, 6),
        "scope_coverage": round(attributed_ns / total_ns, 6)
        if total_ns else 0.0,
        "device_steps": len(steps),
        "planes": n_planes,
        "peaks": {"flops": peak_f, "bytes_per_s": peak_b},
        "modules": modules,
        "scopes": out_scopes,
    }


#: the gap-report entry schema. ``tools/lint_instrumentation.py``
#: rule 8 resolves every ``gap.<key>`` token in docs/OPS.md and
#: tools/tpu_watch.py against THIS tuple — extend it here first.
#: ``closed_by`` (ISSUE 15): the registered fused kernel
#: (``ops/kernel_registry.py``) this scope now dispatches to, or None
#: while the gap is open — a closed scope is never a candidate and its
#: ``dl4j_tpu_devtime_scope_pallas_candidate`` gauge reads 0.
#: ``comm_ms`` (ISSUE 17): device time the scope spent inside
#: collective ops — when it dominates, ``bound`` reads ``"wire"`` (the
#: interconnect, not a kernel, is the ceiling) and the scope is never
#: a Pallas candidate.
GAP_KEYS = ("scope", "device_ms", "share", "ops", "fusions",
            "backward_ms", "comm_ms", "flops", "bytes", "utilization",
            "bound", "pallas_candidate", "closed_by")

#: a scope whose collective time exceeds this fraction of its device
#: time is wire-bound (the gap report + commtime WIRE_BOUND alarm)
WIRE_BOUND_SHARE = 0.5


def _is_pallas_candidate(share: float, util: Optional[float],
                         custom_ms: float, device_ms: float) -> bool:
    """A scope is worth a Pallas kernel when it is a real share of the
    step AND the roofline says XLA left performance on the table — and
    it is not already dominated by a custom call (an existing Pallas
    kernel re-flagging itself forever)."""
    if device_ms > 0 and custom_ms > 0.5 * device_ms:
        return False
    if util is None:                # no cost info: share alone decides
        return share >= 0.10
    return share >= 0.05 and util < 0.35


def gap_report(capture_: Dict[str, Any], top: int = 12
               ) -> List[Dict[str, Any]]:
    """Rank the capture's scopes by device-time share; every entry
    carries exactly :data:`GAP_KEYS`. A scope covered by a registered
    (gate-active) fused kernel reports that kernel as ``closed_by``
    and is never a ``pallas_candidate`` — the loop-closing half of the
    observatory: the report that NAMED the gap is also the proof the
    gap was filled (``tools/perf_dossier.py`` ``hot_path_gaps`` prints
    the closed/open split)."""
    from deeplearning4j_tpu.ops import kernel_registry
    rows = []
    for name, e in capture_["scopes"].items():
        rl = e.get("roofline")
        util = rl["utilization"] if rl else None
        bound = rl["bound"] if rl else "unknown"
        comm_ms = e.get("comm_ms", 0.0)
        # the comm axis: collective-dominated scopes are WIRE-bound —
        # the interconnect is the ceiling, so no kernel closes them
        wire = (e["device_ms"] > 0
                and comm_ms > WIRE_BOUND_SHARE * e["device_ms"])
        if wire:
            bound = "wire"
        closed = kernel_registry.closed_by(name)
        rows.append({
            "scope": name,
            "device_ms": e["device_ms"],
            "share": e["share"],
            "ops": e["ops"],
            "fusions": e["fusions"],
            "backward_ms": e["backward_ms"],
            "comm_ms": comm_ms,
            "flops": e["flops"],
            "bytes": e["bytes"],
            "utilization": util,
            "bound": bound,
            "pallas_candidate": closed is None and not wire
            and _is_pallas_candidate(
                e["share"], util, e["custom_call_ms"], e["device_ms"]),
            "closed_by": closed,
        })
    rows.sort(key=lambda r: -r["share"])
    assert all(tuple(r) == GAP_KEYS for r in rows)
    return rows[:top]


def _publish(capture_: Dict[str, Any],
             gaps: List[Dict[str, Any]]) -> None:
    """Export the last capture as ``dl4j_tpu_devtime_*`` gauges.
    Scope-label cardinality is bounded by the gap report's ``top``;
    stale labels from the previous capture are dropped so the scrape
    always shows ONE capture's ranking."""
    for fam in (_metrics.DEVTIME_SCOPE_SECONDS,
                _metrics.DEVTIME_SCOPE_SHARE,
                _metrics.DEVTIME_SCOPE_UTILIZATION,
                _metrics.DEVTIME_SCOPE_CANDIDATE):
        with fam._lock:
            fam._children.clear()
    for g in gaps:
        lab = g["scope"]
        _metrics.DEVTIME_SCOPE_SECONDS.labels(scope=lab).set(
            g["device_ms"] / 1e3)
        _metrics.DEVTIME_SCOPE_SHARE.labels(scope=lab).set(g["share"])
        if g["utilization"] is not None:
            _metrics.DEVTIME_SCOPE_UTILIZATION.labels(scope=lab).set(
                g["utilization"])
        _metrics.DEVTIME_SCOPE_CANDIDATE.labels(scope=lab).set(
            int(g["pallas_candidate"]))
    _metrics.DEVTIME_PALLAS_CANDIDATES.set(
        sum(1 for g in gaps if g["pallas_candidate"]))


# ---------------------------------------------------------------------------
# capture pipelines: on demand + cadence
# ---------------------------------------------------------------------------

def capture(run, *, executables: Iterable[Any] = (),
            label: str = "on_demand", top: int = 12,
            keep_dir: Optional[str] = None) -> Dict[str, Any]:
    """The on-demand pipeline: run ``run()`` (real steps — the capture
    measures whatever the caller dispatches) under a
    ``jax.profiler.trace`` window, attribute the device time against
    ``executables``' scope maps, publish the gauges, and return
    ``{"capture": ..., "gaps": [...]}``. ``keep_dir`` preserves the
    raw xplane session for ``tools/xprof_summary.py``."""
    import jax

    d = keep_dir or tempfile.mkdtemp(prefix="dl4j_devtime_")
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
        att = attribute(xplane_paths(d),
                        maps=executable_maps(executables))
    finally:
        if keep_dir is None:
            shutil.rmtree(d, ignore_errors=True)
    wall = _trace.now() - t0
    gaps = gap_report(att, top=top)
    with _lock:
        _counters["captures"] += 1
    _metrics.DEVTIME_CAPTURES.inc()
    _metrics.DEVTIME_CAPTURE_SECONDS.inc(wall)
    _publish(att, gaps)
    global _last_report
    _last_report = {"label": label, "capture_wall_s": round(wall, 6),
                    "capture": att, "gaps": gaps}
    if _trace.enabled():
        _trace.instant("devtime/capture",
                       {"label": label, "wall_s": round(wall, 4)})
    return _last_report


class Observatory:
    """Cadence-gated capture windows inside the fit loops: every
    ``every``-th iteration opens a ``jax.profiler.trace`` window that
    stays open for ``steps`` fit steps, then attributes and publishes.
    Instantiated from ``DL4J_TPU_DEVTIME`` — never on the default
    path."""

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
        d = tempfile.mkdtemp(prefix="dl4j_devtime_")
        try:
            jax.profiler.start_trace(d)
        except Exception:
            # another profiler session owns the process (e.g. the
            # dossier's --trace wrapper): skip this window, never
            # break the step
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
            att = attribute(
                xplane_paths(d),
                maps=executable_maps(
                    sentry_executables(*[f for f in step_fns
                                         if f is not None])))
        except FileNotFoundError:
            shutil.rmtree(d, ignore_errors=True)
            return
        finally:
            shutil.rmtree(d, ignore_errors=True)
        wall = _trace.now() - self._t0
        gaps = gap_report(att, top=self.top)
        with _lock:
            _counters["captures"] += 1
        _metrics.DEVTIME_CAPTURES.inc()
        _metrics.DEVTIME_CAPTURE_SECONDS.inc(wall)
        _publish(att, gaps)
        global _last_report
        _last_report = {"label": "cadence",
                        "capture_wall_s": round(wall, 6),
                        "capture": att, "gaps": gaps}


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
    """Install the monitor from ``DL4J_TPU_DEVTIME`` (called by
    ``environment.apply_startup_flags``; the unset path never reaches
    here)."""
    from deeplearning4j_tpu import environment
    raw = str(environment.get_flag("DL4J_TPU_DEVTIME") or "").strip()
    if raw.lower() not in _TRUTHY:
        return None
    return configure(
        every=int(environment.get_flag("DL4J_TPU_DEVTIME_EVERY")),
        steps=int(environment.get_flag("DL4J_TPU_DEVTIME_STEPS")))


# -- fit-loop hooks (the counter-fenced off path) ---------------------------

def step_started(iteration: int) -> None:
    """Called by the fit loops before dispatching a step. Off path
    (``DL4J_TPU_DEVTIME`` unset): one module-global ``is None``
    branch — zero profiler sessions, zero allocations."""
    m = _MONITOR
    if m is None:
        return
    m.on_step_start(iteration)


def step_ended(*step_fns) -> None:
    """Called by the fit loops after the step's blocking sync, passing
    the step's (possibly warmed) ``sentry.jit`` entry points so the
    attribution can read their compiled HLO. Same one-branch off
    path."""
    m = _MONITOR
    if m is None:
        return
    m.on_step_end(*step_fns)


# ---------------------------------------------------------------------------
# bench probe
# ---------------------------------------------------------------------------

def measure_capture_overhead(step_seconds: Optional[float] = None,
                             iters: int = 20000) -> Dict[str, Any]:
    """The ``devtime`` section of ``bench.py``/the dossier: the OFF
    path (the two fit-loop hook branches every un-observed step pays)
    and the capture counters — synthetic probe state restored so the
    off-path fences stay honest."""
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
                               "top_gap": (lr["gaps"][0]["scope"]
                                           if lr["gaps"] else None)}
    return out


__all__ = ["scope", "capture", "attribute", "gap_report", "roofline",
           "read_xspace", "xplane_paths", "op_events",
           "step_durations_ns", "hlo_scope_map", "executable_maps",
           "sentry_executables", "peaks_from_env", "Observatory",
           "configure", "configure_from_env", "disable",
           "step_started", "step_ended", "captures",
           "profiler_sessions", "reset_counters", "last_report",
           "measure_capture_overhead", "GAP_KEYS", "SCOPE_PREFIX",
           "COLLECTIVE_KINDS", "collective_kind", "WIRE_BOUND_SHARE"]

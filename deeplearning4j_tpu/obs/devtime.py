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
   wire-format reader (:func:`read_xspace` — the pinned
   ``jax.profiler.ProfileData`` shows an event's own stats but not
   those of its METADATA entry, where a TPU keeps an op's program and
   framework path, and the tensorboard plugin's proto module is absent
   from the wheel); ``tools/xprof_summary.py`` reads captures through
   the same parser.

3. **Attribution** (:func:`joined_events`, THE join: the operator's
   :func:`capture` / :func:`gap_report` and the benchmark's
   ``readers/trace_scope.py`` read the same one). The trace says
   itself what ran under which scope, in two forms
   (:func:`op_events`): a TPU's "XLA Ops" event is named by its whole
   HLO instruction, lies inside the "XLA Modules" event of its program
   (``jit_admit(<program id>)``: programs that share a name are told
   apart by the id, never by the name), nests its loop bodies (time is
   SELF time), and its metadata carries the framework op path with
   every ``dl4j.`` scope on it, beside XLA's own operation and byte
   counts; the CPU's thunk events carry ``hlo_op`` / ``hlo_module`` /
   ``program_id`` and no path. In both, the trace's
   ``/host:metadata`` plane holds each executed program's
   ``HloProto`` (:func:`trace_scope_maps`): an instruction without a
   scope of its own takes its consumer's, its caller's or its body's
   (:func:`_resolve_scopes`). Nothing is asked of the process that ran
   the programs and nothing is kept at compile or warm-up time; maps
   made of ``Compiled.as_text()`` (``executables=``, the retrace
   sentry keeps its AOT executables, :func:`sentry_executables`) only
   add per-op FLOP/byte estimates parsed from the HLO shapes where the
   trace has none, and ``Compiled.cost_analysis()`` program totals for
   the per-module cross-check (the ``modules`` section). Each scope
   gets an achieved-vs-roofline utilization (:func:`roofline`, peaks
   by ``device_kind`` from ``environment.DEVICE_PEAKS``).

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
from bisect import bisect_right
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
#   XEventMetadata{id=1,name=2,display_name=4,stats=5};
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

        {"planes": [{"name", "programs": {id: {"name", "stats"}},
                     "lines": [{"name", "timestamp_ns",
                     "events": [{"name", "dur_ps", "offset_ps",
                                 "stats": {...}, "meta": {...}}]}]}]}

    Event names and ref-valued stats are resolved through the plane's
    metadata tables. ``meta`` holds the stats of the event's METADATA
    entry (one dict shared by every event of that entry): on a TPU that
    is where an op's framework path (``tf_op``), ``program_id``,
    ``flops`` and ``bytes_accessed`` live; the event's own stats are
    its timing alone. ``programs`` is the plane's metadata table as it
    stands where the plane has no line: the ``/host:metadata`` plane,
    whose entries hold each executed program's ``Hlo Proto`` under its
    program id."""
    buf = Path(path).read_bytes()
    planes = []
    for fno, _wt, pbuf in _fields(buf):
        if fno != 1:
            continue
        name = ""
        line_bufs: List[bytes] = []
        meta_bufs: Dict[int, bytes] = {}
        stat_names: Dict[int, str] = {}
        for pf, _pw, pv in _fields(pbuf):
            if pf == 2:
                name = pv.decode("utf-8", "replace")
            elif pf == 3:
                line_bufs.append(pv)
            elif pf == 4:
                k, v = _map_entry(pv)
                meta_bufs[k] = v
            elif pf == 5:
                k, v = _map_entry(pv)
                sm_name = ""
                for sf, _sw, svv in _fields(v):
                    if sf == 2:
                        sm_name = svv.decode("utf-8", "replace")
                stat_names[k] = sm_name
        # the stat names may follow the event metadata in the file
        ev_meta: Dict[int, Tuple[str, Dict[str, Any]]] = {}
        for k, v in meta_bufs.items():
            em_name, em_stats = "", {}
            for ef, _ew, evv in _fields(v):
                if ef == 2:
                    em_name = evv.decode("utf-8", "replace")
                elif ef == 5:
                    sk, sv = _stat(evv, stat_names)
                    em_stats[sk] = sv
            ev_meta[k] = (em_name, em_stats)
        none = ("", {})
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
                    em_name, em_stats = ev_meta.get(mid, none)
                    events.append({"name": em_name or str(mid),
                                   "offset_ps": off_ps,
                                   "dur_ps": dur_ps, "stats": stats,
                                   "meta": em_stats})
            lines.append({"name": lname, "timestamp_ns": ts_ns,
                          "events": events})
        plane = {"name": name, "lines": lines}
        if not lines:
            plane["programs"] = {k: {"name": n, "stats": st}
                                 for k, (n, st) in ev_meta.items()}
        planes.append(plane)
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


#: an "XLA Modules" event is named ``<HLO module>(<program id>)``
_PROGRAM_RE = re.compile(r"^(.*)\((\d+)\)$")


def _self_ps(spans: List[Tuple[int, int]]) -> List[int]:
    """Self time of each ``(start, duration)``: its duration less what
    the spans nested inside it cover (on a device's "XLA Ops" line a
    ``while`` holds its body's ops, each inside the one before it or
    after it, never across)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][0], -spans[i][1]))
    own = [d for _s, d in spans]
    stack: List[Tuple[int, int]] = []       # (end, index)
    for i in order:
        s, d = spans[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0]:
            own[stack[-1][1]] -= d
        stack.append((s + d, i))
    return own


def _hbm_bytes(meta: Dict[str, Any]) -> float:
    """The bytes one execution of an instruction moves to or from HBM,
    from its metadata's ``memory_access_breakdown`` (a serialized list
    of ``{operation_type=1, memory_space=2, bytes_accessed=3}``, space
    1 being HBM): ``bytes_accessed`` counts reads of what an async
    copy already brought into on-chip memory too, and a scope of such
    reads would stand above the HBM roofline. Without the breakdown,
    ``bytes_accessed`` as it is."""
    breakdown = meta.get("memory_access_breakdown")
    if not isinstance(breakdown, bytes):
        return float(meta.get("bytes_accessed") or 0)
    total = 0
    for f, _w, entry in _fields(breakdown):
        if f != 1:
            continue
        space = nbytes = 0
        for ef, _ew, v in _fields(entry):
            if ef == 2:
                space = v
            elif ef == 3:
                nbytes = v
        if space == 1:
            total += nbytes
    return float(total)


def op_events(xspace: Dict[str, Any]) -> List[Dict[str, Any]]:
    """XLA-op *execution* events from one parsed xplane, in the two
    forms the pinned JAX writes:

    - **a TPU's device plane**: the "XLA Ops" line. An event is named
      by its whole HLO instruction (``%fusion.12 = bf16[...] fusion(
      ...)``): ``op`` is the head of it, ``text`` the whole. Its
      program is the "XLA Modules" event of the same plane that
      contains it, named ``jit_admit(<program id>)``: ``module`` is
      the name, ``program_id`` the number (several programs may share
      one name: a bucket each), ``launch_ns`` that event's start. The
      event's METADATA gives ``op_name`` (its ``tf_op`` stat: the
      framework path with every ``dl4j.`` scope on it), ``flops``,
      ``bytes`` (:func:`_hbm_bytes`) and ``kind`` (XLA's own,
      ``hlo_category``). A loop
      holds its body, so ``self_ns`` is the duration less the events
      nested inside. An event outside every program whose metadata
      names none has ``module`` ``""``: it cannot be joined.
    - **the CPU thunk executor**: host lines whose events carry
      ``hlo_op`` / ``hlo_module`` / ``program_id`` stats; nothing
      nests (``self_ns`` is ``dur_ns``) and nothing carries a scope.

    Returns ``[{"op", "module", "program_id", "launch_ns",
    "start_ns", "dur_ns", "self_ns", "plane", "device_line", ...},
    ...]``."""
    out = []
    for plane in xspace["planes"]:
        device = "/device:" in plane["name"]
        programs: List[Tuple[int, int, str, int]] = []
        if device:
            for line in plane["lines"]:
                if line["name"] != "XLA Modules":
                    continue
                base = line["timestamp_ns"] * 1000
                for e in line["events"]:
                    m = _PROGRAM_RE.match(e["name"])
                    programs.append((
                        base + e["offset_ps"],
                        base + e["offset_ps"] + e["dur_ps"],
                        m.group(1) if m else e["name"],
                        int(m.group(2)) if m else 0))
            programs.sort()
        starts = [p[0] for p in programs]
        name_of = {p[3]: p[2] for p in programs if p[3]}
        hbm: Dict[int, float] = {}      # by metadata entry
        for line in plane["lines"]:
            base = line["timestamp_ns"] * 1000
            if device and line["name"] == "XLA Ops":
                evs = [e for e in line["events"] if e["dur_ps"]]
                own = _self_ps([(base + e["offset_ps"], e["dur_ps"])
                                for e in evs])
                for e, self_ps in zip(evs, own):
                    start = base + e["offset_ps"]
                    meta = e["meta"]
                    pid = int(meta.get("program_id") or 0)
                    i = bisect_right(starts, start) - 1
                    launch = None
                    if i >= 0 and start < programs[i][1]:
                        launch, _end, module, pid = programs[i]
                    else:
                        module = name_of.get(pid, "")
                    rec = {"op": e["name"].split(" = ", 1)[0].lstrip("%"),
                           "text": e["name"], "module": module,
                           "program_id": pid,
                           "launch_ns": None if launch is None
                           else launch / 1e3,
                           "start_ns": start / 1e3,
                           "dur_ns": e["dur_ps"] / 1e3,
                           "self_ns": self_ps / 1e3,
                           "plane": plane["name"], "device_line": True}
                    if meta.get("tf_op"):
                        rec["op_name"] = str(meta["tf_op"])
                    if "hlo_category" in meta:
                        rec["kind"] = str(meta["hlo_category"])
                        rec["flops"] = float(meta.get("flops") or 0)
                        if id(meta) not in hbm:
                            hbm[id(meta)] = _hbm_bytes(meta)
                        rec["bytes"] = hbm[id(meta)]
                    out.append(rec)
                continue
            for e in line["events"]:
                mod = e["stats"].get("hlo_module")
                if mod is None or not e["dur_ps"]:
                    continue
                dur = e["dur_ps"] / 1e3
                out.append({"op": str(e["stats"].get("hlo_op")
                                      or e["name"]),
                            "module": str(mod),
                            "program_id": int(
                                e["stats"].get("program_id") or 0),
                            "launch_ns": None,
                            "start_ns": (base + e["offset_ps"]) / 1e3,
                            "dur_ns": dur, "self_ns": dur,
                            "plane": plane["name"],
                            "device_line": False})
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


#: instructions that compute nothing: where one carries no ``dl4j.``
#: scope of its own, the time it takes is the time of bringing an
#: operand to whoever reads it (an async slice of a weight matrix
#: awaited in front of its matmul, a parameter's layout copy)
_MOVES = {"copy", "copy-start", "copy-done", "async-start",
          "async-update", "async-done", "slice", "dynamic-slice",
          "bitcast", "get-tuple-element", "tuple", "broadcast",
          "convert", "pad", "reshape", "transpose"}


def _scope_path(op_name: str) -> Tuple[Tuple[str, ...], bool]:
    """Every ``dl4j.`` scope on one framework op path, outermost
    first and each once (a nested ``jit`` repeats its caller's), and
    whether the path is a backward one."""
    return (tuple(dict.fromkeys(_SCOPE_RE.findall(op_name))),
            "transpose(" in op_name)


def _resolve_scopes(instrs: List[Dict[str, Any]]) -> None:
    """Give every instruction of one program its scope path, in
    program order ``[{"name", "comp", "kind", "named", "path",
    "backward", "operands", "callees"}, ...]`` (``named``: it has a
    framework path of its own, scoped or not); sets ``via`` to how it was found:

    - ``own``: the instruction's own ``metadata op_name`` holds it;
    - ``consumer``: it only moves data (:data:`_MOVES`, or a custom
      call the compiler made itself, with no framework path at all:
      the TPU's ``ConcatBitcast`` of a weight's async slices) and the
      first instruction of its computation that reads what it moved
      has a scope: a ``slice-done`` the device waits in is time of
      the matmul behind it;
    - ``caller``: the instruction that calls its computation has one
      (a loop body's bookkeeping: XLA:CPU's scatter loops);
    - ``body``: a ``while`` / ``conditional`` / ``call`` of its own
      takes the first scope found inside what it calls (only its own
      few microseconds are booked there, never its body's time).

    An instruction none of these reaches keeps an empty path."""
    members: Dict[str, List[Dict[str, Any]]] = {}
    users: Dict[str, List[Dict[str, Any]]] = {}
    caller_of: Dict[str, Dict[str, Any]] = {}
    for ins in instrs:
        members.setdefault(ins["comp"], []).append(ins)
        for o in ins["operands"]:
            users.setdefault(o, []).append(ins)
        for c in ins["callees"]:
            caller_of.setdefault(c, ins)
    memo: Dict[Tuple[str, bool], Any] = {}

    def inside(comp: str, depth: int):
        for ins in members.get(comp, ()):
            if ins["path"]:
                return ins["path"], ins["backward"]
        if depth < 4:
            for ins in members.get(comp, ()):
                for c in ins["callees"]:
                    got = inside(c, depth + 1)
                    if got:
                        return got
        return None

    def resolve(ins, depth: int, body_ok: bool):
        if ins["path"]:
            return ins["path"], ins["backward"], "own"
        key = (ins["name"], body_ok)
        if key in memo or depth > 8:
            return memo.get(key)
        memo[key] = None                    # a cycle finds nothing
        got = None
        if ins["kind"] in _MOVES or (ins["kind"] == "custom-call"
                                     and not ins["named"]):
            for user in users.get(ins["name"], ()):
                if user["comp"] != ins["comp"]:
                    continue
                r = resolve(user, depth + 1, False)
                if r:
                    got = (r[0], r[1], "consumer")
                    break
        caller = caller_of.get(ins["comp"])
        if got is None and caller is not None and caller is not ins:
            r = resolve(caller, depth + 1, False)
            if r:
                got = (r[0], ins["backward"] or r[1], "caller")
        if got is None and body_ok and ins["kind"] in _CONTAINER_KINDS:
            for c in ins["callees"]:
                r = inside(c, 0)
                if r:
                    got = (r[0], r[1], "body")
                    break
        memo[key] = got
        return got

    for ins in instrs:
        r = resolve(ins, 0, True)
        ins["via"] = r[2] if r else None
        if r and not ins["path"]:
            ins["path"], ins["backward"] = r[0], r[1]


def _scope_map(module: str, instrs: List[Dict[str, Any]]
               ) -> Dict[str, Any]:
    _resolve_scopes(instrs)
    return {"module": module, "ops": {
        i["name"]: {"scope": i["path"][-1] if i["path"] else None,
                    "path": i["path"], "via": i["via"],
                    "backward": i["backward"], "kind": i["kind"],
                    "flops": i["flops"], "bytes": i["bytes"]}
        for i in instrs}}


def hlo_scope_map(hlo_text: str) -> Dict[str, Any]:
    """Map one executable's post-optimization HLO to attribution data:
    ``{"module": name, "ops": {op_name: {"scope", "path", "via",
    "backward", "kind", "flops", "bytes"}}}``. ``path`` is every
    ``dl4j.`` scope on the op's ``metadata op_name``, outermost first,
    ``scope`` the INNERMOST of them. An op with none of its own takes
    one from its consumer, its caller or its body
    (:func:`_resolve_scopes`; ``via`` says which): XLA:CPU's scatter
    loops are made of un-annotated body ops, so a conv-backward
    scatter's thousands of iterations attribute to the conv layer, not
    to noise. None when nothing on those chains is annotated
    (optimizer update, loss, ...)."""
    m = _HLO_MODULE_RE.search(hlo_text)
    module = m.group(1) if m else ""
    instrs: List[Dict[str, Any]] = []
    shape_of: Dict[str, Tuple[str, str]] = {}   # op -> result shape
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
        body = rhs.split(", metadata=", 1)[0]
        callees = _CALLEE_RE.findall(body)
        nm = _OP_NAME_RE.search(rhs)
        path, backward = _scope_path(nm.group(1)) if nm else ((), False)
        shapes = _line_shapes(kind, rhs, shape_of)
        flops, bytes_ = _op_cost(kind, rhs, shapes)
        instrs.append({"name": op, "comp": current_comp, "kind": kind,
                       "named": nm is not None,
                       "path": path, "backward": backward,
                       "operands": [n for n in _OPERAND_RE.findall(body)
                                    if n not in callees],
                       "callees": callees,
                       "flops": flops, "bytes": bytes_})
    return _scope_map(module, instrs)


# Field numbers from xla/service/hlo.proto and xla/xla_data.proto:
#   HloProto.hlo_module=1; HloModuleProto{name=1,computations=3};
#   HloComputationProto{name=1,instructions=2,id=5};
#   HloInstructionProto{name=1,opcode=2,metadata=7,id=35,
#   operand_ids=36,called_computation_ids=38}; OpMetadata.op_name=2.

def _int64s(wire_type: int, value) -> List[int]:
    """A repeated int64 field's values, packed or not."""
    if wire_type == 0:
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_proto_scope_map(proto: bytes) -> Dict[str, Any]:
    """:func:`hlo_scope_map` of a serialized ``HloProto``: what a
    trace's ``/host:metadata`` plane holds of each program it saw
    run. No shapes are read: the TPU's op events carry XLA's own
    operation and byte counts, so ``flops`` and ``bytes`` are 0 here."""
    module = ""
    comps: List[Tuple[int, str, List[bytes]]] = []
    for f, _w, v in _fields(proto):
        if f != 1:
            continue
        for mf, _mw, mv in _fields(v):
            if mf == 1:
                module = mv.decode("utf-8", "replace")
            elif mf == 3:
                cid, cname, ibufs = 0, "", []
                for cf, _cw, cv in _fields(mv):
                    if cf == 1:
                        cname = cv.decode("utf-8", "replace")
                    elif cf == 2:
                        ibufs.append(cv)
                    elif cf == 5:
                        cid = cv
                comps.append((cid, cname, ibufs))
    comp_name = {cid: cname for cid, cname, _ in comps}
    instrs: List[Dict[str, Any]] = []
    name_of: Dict[int, str] = {}
    for _cid, cname, ibufs in comps:
        for ibuf in ibufs:
            ins = {"name": "", "comp": cname, "kind": "", "path": (),
                   "named": False,
                   "backward": False, "operands": [], "callees": [],
                   "flops": 0.0, "bytes": 0.0}
            iid = 0
            for f, w, v in _fields(ibuf):
                if f == 1:
                    ins["name"] = v.decode("utf-8", "replace")
                elif f == 2:
                    ins["kind"] = v.decode("utf-8", "replace")
                elif f == 7:
                    for mf, _mw, mv in _fields(v):
                        if mf == 2 and mv:
                            ins["named"] = True
                            ins["path"], ins["backward"] = _scope_path(
                                mv.decode("utf-8", "replace"))
                elif f == 35:
                    iid = v
                elif f == 36:
                    ins["operands"] += _int64s(w, v)
                elif f == 38:
                    ins["callees"] += _int64s(w, v)
            name_of[iid] = ins["name"]
            if ins["kind"] != "parameter":
                instrs.append(ins)
    for ins in instrs:
        ins["operands"] = [name_of[o] for o in ins["operands"]
                           if o in name_of]
        ins["callees"] = [comp_name[c] for c in ins["callees"]
                          if c in comp_name]
    return _scope_map(module, instrs)


def trace_scope_maps(xspace: Dict[str, Any],
                     program_ids: Optional[Iterable[int]] = None
                     ) -> Dict[int, Any]:
    """Scope maps of the programs one trace saw run, from the trace
    itself, keyed by program id: its ``/host:metadata`` plane holds
    each program's ``HloProto``. Nothing is asked of the process that
    ran them, so a trace read after its owner was freed, or on another
    machine, joins all the same. ``program_ids`` limits the parsing to
    the programs wanted (a proto of 1.7 MB takes a quarter second)."""
    wanted = None if program_ids is None else set(program_ids)
    maps: Dict[int, Any] = {}
    for plane in xspace["planes"]:
        if plane["name"] != "/host:metadata":
            continue
        for pid, entry in plane.get("programs", {}).items():
            proto = entry["stats"].get("Hlo Proto")
            if isinstance(proto, bytes) and (wanted is None
                                             or pid in wanted):
                maps[pid] = hlo_proto_scope_map(proto)
    return maps


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
    ``cost_analysis()`` program totals per module. ``programs`` counts
    the executables that came under one name (the gateway's prefill
    buckets are all ``jit_admit``): the join takes a map by its name
    only where that is 1."""
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
        sm["programs"] = 1 + maps.get(sm["module"],
                                      {"programs": 0})["programs"]
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


#: the scope of an event whose program the trace does not tell
UNJOINED = "unjoined"


def joined_events(paths: Iterable[str],
                  maps: Optional[Dict[Any, Any]] = None
                  ) -> List[Dict[str, Any]]:
    """THE join: every op event of ``paths`` (:func:`op_events`) with
    the scope it ran under. Each event gains ``path`` (its ``dl4j.``
    scopes, outermost first; empty where it has none), ``scope`` (the
    innermost, or None), ``via`` (where the path came from: ``trace``,
    the event's own framework path, else :func:`_resolve_scopes`'s
    word), ``backward``, ``kind``, ``flops`` and ``bytes``.

    The path's sources, in this order: the framework op path the TPU's
    trace holds with the event; the program's map by its PROGRAM ID,
    read from the trace's own ``/host:metadata`` plane
    (:func:`trace_scope_maps`); a map of ``maps`` by the program's
    NAME, and that only where one program of that name is in ``maps``
    and one in the trace: two buckets of ``jit_admit`` hold a
    ``fusion.12`` each, and a name alone never says whose. An event
    whose program cannot be told at all (``module`` empty) gets the
    scope :data:`UNJOINED`, never another program's."""
    out: List[Dict[str, Any]] = []
    for p in paths:
        out.extend(_join_xspace(read_xspace(p), maps or {}))
    return out


def _join_xspace(xs: Dict[str, Any], maps: Dict[Any, Any]
                 ) -> List[Dict[str, Any]]:
    events = op_events(xs)
    ids_of: Dict[str, set] = {}
    for ev in events:
        ids_of.setdefault(ev["module"], set()).add(ev["program_id"])
    by_id = trace_scope_maps(
        xs, {i for ids in ids_of.values() for i in ids})
    for ev in events:
        own = by_id.get(ev["program_id"])
        named = maps.get(ev["module"]) if ev["module"] else None
        if named is not None and (named.get("programs", 1) != 1
                                  or len(ids_of[ev["module"]]) != 1):
            named = None
        # the trace's own map says whose instruction it is; a map made
        # of an executable also estimates its operations and bytes
        info = cost = None
        for m in (own, named):
            if m is not None and ev["op"] in m["ops"]:
                info = info or m["ops"][ev["op"]]
                cost = m["ops"][ev["op"]]
        path, backward, via = (), False, None
        if "op_name" in ev:
            path, backward = _scope_path(ev["op_name"])
            via = "trace" if path else None
        if not path and info is not None and info["path"]:
            path, backward, via = (info["path"], info["backward"],
                                   info["via"])
        if not ev["module"]:
            path, via = (UNJOINED,), None
        ev["path"], ev["scope"] = path, (path[-1] if path else None)
        ev["via"], ev["backward"] = via, backward
        ev["map"] = named or own
        ev.setdefault("kind", info["kind"] if info is not None
                      else _op_class(ev["op"]))
        ev.setdefault("flops", cost["flops"] if cost else 0.0)
        ev.setdefault("bytes", cost["bytes"] if cost else 0.0)
    return events


def attribute(paths: Iterable[str],
              maps: Optional[Dict[str, Any]] = None,
              peaks: Optional[Tuple[float, float]] = None
              ) -> Dict[str, Any]:
    """Per-scope device-time totals of ``paths`` (xplane files — every
    host of one session) from :func:`joined_events`, by SELF time (a
    loop on a device's line holds its body's ops; on the CPU's host
    lines nothing nests and a container is left out). Ops outside
    every annotated region aggregate under ``op:<class>`` scopes (the
    xprof class view), ops whose program cannot be told under
    :data:`UNJOINED`, so the report always accounts for 100% of
    measured device time."""
    peak_f, peak_b = peaks or peaks_from_env()
    scopes: Dict[str, Dict[str, Any]] = {}
    module_ns: Dict[Any, float] = {}
    module_op_count: Dict[Tuple[Any, str], int] = {}
    module_launches: Dict[Any, set] = {}
    module_map: Dict[Any, Any] = {}
    total_ns = 0.0
    attributed_ns = 0.0
    steps: List[float] = []
    n_planes = 0
    events: List[Dict[str, Any]] = []
    for p in paths:
        xs = read_xspace(p)
        n_planes += len(xs["planes"])
        steps.extend(step_durations_ns(xs))
        events.extend(_join_xspace(xs, maps or {}))
    for ev in events:
        kind = ev["kind"]
        if kind in _CONTAINER_KINDS and not ev["device_line"]:
            continue                # children report their own time
        sc = ev["scope"]
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
        dur = ev["self_ns"]
        total_ns += dur
        if ev["module"]:
            mk = (ev["module"], ev["program_id"])
            module_ns[mk] = module_ns.get(mk, 0.0) + dur
            module_map[mk] = ev["map"]
            if ev["launch_ns"] is not None:
                module_launches.setdefault(mk, set()).add(
                    (ev["plane"], ev["launch_ns"]))
            elif ev["map"] is not None:
                ok = (mk, ev["op"])
                module_op_count[ok] = module_op_count.get(ok, 0) + 1
        e["device_ns"] += dur
        e["ops"] += 1
        e["kinds"][kind] = e["kinds"].get(kind, 0) + 1
        if "fusion" in kind or "fusion" in ev["op"]:
            e["fusions"] += 1
        if "custom-call" in kind or "custom-call" in ev["op"]:
            e["custom_call_ns"] += dur
        if collective_kind(kind) or collective_kind(ev["op"]):
            e["collective_ns"] += dur
        e["flops"] += ev["flops"]
        e["bytes"] += ev["bytes"]
        if ev["backward"]:
            e["backward_ns"] += dur
        if sc is not None and sc != UNJOINED:
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
    # executed program against its measured device time — the roofline
    # number that does not depend on the regex shape estimates.
    # Executions of a program = its events on the "XLA Modules" line
    # where the trace has one (a TPU); else the MIN occurrence count
    # over its mapped non-container ops in the window: every top-level
    # op runs exactly once per execution (count == executions),
    # loop-body ops run more — min is robust to loop overcount and only
    # underestimates for conditional arms, which merely makes the
    # per-execution roofline conservative. Programs that share a name
    # are listed apart, as ``name(program id)``.
    modules: Dict[str, Dict[str, Any]] = {}
    sharing = [name for name, _pid in module_ns]
    for mk, ns in module_ns.items():
        mm = module_map[mk] or {}
        if mk in module_launches:
            execs = len(module_launches[mk])
        elif not mm:
            continue
        else:
            counts = [c for (m, op), c in module_op_count.items()
                      if m == mk and op in mm["ops"]
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
        modules[mk[0] if sharing.count(mk[0]) == 1
                else f"{mk[0]}({mk[1]})"] = rec
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
           "read_xspace", "xplane_paths", "op_events", "joined_events",
           "step_durations_ns", "hlo_scope_map", "hlo_proto_scope_map",
           "trace_scope_maps", "executable_maps", "UNJOINED",
           "sentry_executables", "peaks_from_env", "Observatory",
           "configure", "configure_from_env", "disable",
           "step_started", "step_ended", "captures",
           "profiler_sessions", "reset_counters", "last_report",
           "measure_capture_overhead", "GAP_KEYS", "SCOPE_PREFIX",
           "COLLECTIVE_KINDS", "collective_kind", "WIRE_BOUND_SHARE"]

"""Process-wide record ring and its Chrome-trace/Perfetto exporter.

Reference observability (SURVEY §5) times the step from the *outside*
(StatsListener wall clocks, PerformanceListener iter/sec); a compiled
stack needs the *inside* view too: where a step's wall time went —
ETL wait vs. host→device transfer vs. async dispatch vs. the blocking
device sync — across every thread (fit loop, prefetch worker, serving
worker). PyGraph (PAPERS.md) makes the same argument for compiled
execution: opaque compiled regions must export structured runtime
evidence or regressions hide inside them.

Design:

- **One clock.** :func:`now` (``time.perf_counter``) is the only step
  clock in the package — ``tools/lint_instrumentation.py`` enforces
  that no module outside ``obs/`` calls ``time.time()`` for timing.
  :func:`clock` takes an anchor ``(perf_counter s, Unix-epoch ns)``
  and :func:`to_epoch_ns` maps a stamp through it, which is how the
  records are laid beside a profiler trace (an xplane's events count
  nanoseconds from its ``profile_start_time``, an epoch time).
- **The ring is always on; the flag gates export.** The instrumented
  paths (:func:`record`, :func:`record_phases`; ``obs.record_step`` and
  its siblings call them) append ONE plain tuple a record to a bounded
  ``deque`` (``DL4J_TPU_TRACE_RING``): no lock of the tracer's own, no
  event dict, no JSON, no file. A record is :class:`Record`: what was
  done, on which thread, the id of what caused it, its stamps, and
  counts. The ring counts what it overwrote (:func:`dropped`), and
  :func:`records` refuses a window whose start is gone.
- **One exporter.** A record becomes Chrome events in :func:`expand`
  and nowhere else: a phased record fans out into ``<name>/step`` and
  one ``<name>/<phase>`` span a phase, a request into its async track.
  Under ``DL4J_TPU_TRACE`` each record is expanded and written as it
  is made (the live file ``tools/tpu_watch.py`` tails; this is the cost
  of tracing ON); :func:`events`, ``obs.report()`` and the crash dump
  expand the ring's tail on demand, flag or no flag.
- **Chrome-trace JSONL.** First line ``[``, then one event object per
  line with a trailing comma — the Chrome trace "JSON array format",
  which explicitly tolerates the missing ``]``, so the file drops
  straight into ``chrome://tracing`` / Perfetto *and* stays
  line-parseable (:func:`read_trace`). The first event is the clock
  anchor (``clock_anchor`` metadata), so the file opens beside an
  xplane. Nesting needs no explicit parent ids: the viewers nest spans
  of one ``tid`` by interval containment.
- **The generic span API stays gated.** :func:`span`, :func:`add_span`,
  :func:`counter` and :func:`instant` (diagnostics off the measured
  paths) record only while :func:`enabled`: one branch when off.

Flags (``environment.py``): ``DL4J_TPU_TRACE`` — '' (no export,
default), truthy ('1'/'true'/'on') for a default
``dl4j_tpu_trace_<pid>.jsonl`` in the cwd, or an explicit output path.
``DL4J_TPU_TRACE_RING`` — the ring's size in records.
"""
from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from deeplearning4j_tpu import environment

now = time.perf_counter     #: the package's step clock (monotonic s)

_TRUTHY = {"1", "true", "on", "yes"}
_FALSEY = {"", "0", "off", "none", "false", "no"}

#: ``ph`` of a request record, whose stamps are ``(t_submit, t_admit,
#: t_first, t_last, t_done)`` and whose ``cause`` is its rid: the
#: exporter writes it as one async track (``b``/``e`` pairs)
REQUEST = "b"


class Record(NamedTuple):
    """One ring entry. ``ph`` is the Chrome phase the exporter starts
    from (``X`` span, ``b`` request track, ``i`` instant, ``C``
    counter); ``stamps`` are :func:`now` seconds, two for a plain span
    and one more than ``phases`` for a phased one; ``cause`` is the id
    of what caused it (a fit call's first iteration, the gateway
    loop's iteration, a request's rid, a sentried function's name)."""
    seq: int
    ph: str
    name: str
    tid: int
    cause: Any
    stamps: Tuple[float, ...]
    phases: Optional[Tuple[str, ...]]
    counts: Optional[Dict[str, Any]]


class Anchor(NamedTuple):
    perf_s: float       #: :func:`now` at the anchor
    epoch_ns: int       #: ``time.time_ns()`` at the same instant
    width_s: float      #: how far apart the two readings can be


def _ring_size() -> int:
    return max(1, int(environment.get_flag("DL4J_TPU_TRACE_RING")))


_lock = threading.Lock()    # the exporter's file, never the ring
_enabled = False            # export / verbose span API on
_ring: deque = deque(maxlen=_ring_size())
_seq = itertools.count()    # next() is atomic under the GIL
_fh = None                  # open JSONL handle (None -> ring only)
_path: Optional[str] = None
_events_recorded = 0        # Chrome events the exporter wrote
_seen_tids: set = set()     # threads announced in the open file
_thread_names: Dict[int, str] = {}


def clock(tries: int = 5) -> Anchor:
    """An anchor between the step clock and the Unix epoch: the two
    read back to back, the tightest of a few tries."""
    best = None
    for _ in range(tries):
        a = now()
        e = time.time_ns()
        b = now()
        if best is None or b - a < best.width_s:
            best = Anchor((a + b) / 2, e, b - a)
    return best


_anchor = clock()           # the process's own, taken at import


def anchor() -> Anchor:
    """The process's own anchor, taken when this module was imported:
    with a fresh :func:`clock` it gives the two clocks' drift."""
    return _anchor


def to_epoch_ns(t: float, anchor: Optional[Anchor] = None) -> int:
    """A :func:`now` stamp as Unix-epoch nanoseconds."""
    a = anchor or _anchor
    return a.epoch_ns + int(round((t - a.perf_s) * 1e9))


def from_epoch_ns(ns: int, anchor: Optional[Anchor] = None) -> float:
    """Unix-epoch nanoseconds as a :func:`now` stamp."""
    a = anchor or _anchor
    return a.perf_s + (ns - a.epoch_ns) / 1e9


def enabled() -> bool:
    return _enabled


def enable(path: Optional[str] = None,
           ring: Optional[int] = None) -> Optional[str]:
    """Turn export on. ``path`` (optional) streams every record, as
    Chrome events, to a JSONL file; without it only the gated span API
    joins the ring. ``ring`` resizes the ring (its tail is kept).
    Returns the active file path (None when ring-only)."""
    global _enabled, _ring, _fh, _path
    with _lock:
        _close_locked()
        if ring is not None:
            _ring = deque(_ring, maxlen=max(1, int(ring)))
        _seen_tids.clear()
        if path is not None:
            _path = os.fspath(path)
            fh = open(_path, "w")
            fh.write("[\n")     # Chrome JSON array format (']' optional)
            fh.write(json.dumps(
                {"ph": "M", "name": "clock_anchor", "pid": os.getpid(),
                 "args": {"perf_counter_s": _anchor.perf_s,
                          "epoch_ns": _anchor.epoch_ns}},
                separators=(",", ":")) + ",\n")
            _fh = fh
        else:
            _path = None
        _enabled = True
    return _path


def disable() -> None:
    """Stop exporting and close the output file (the ring goes on)."""
    global _enabled
    with _lock:
        _enabled = False
        _close_locked()


def _close_locked() -> None:
    global _fh
    if _fh is not None:
        try:
            _fh.flush()
            _fh.close()
        except OSError:
            pass
        _fh = None


def configure_from_env() -> Optional[str]:
    """Start the exporter from ``DL4J_TPU_TRACE`` (called by
    ``environment.apply_startup_flags`` at package import). Truthy →
    default per-pid file; any other non-falsey value → output path."""
    raw = str(environment.get_flag("DL4J_TPU_TRACE")).strip()
    if raw.lower() in _FALSEY:
        return None
    if raw.lower() in _TRUTHY:
        return enable(f"dl4j_tpu_trace_{os.getpid()}.jsonl")
    return enable(raw)


def reset() -> None:
    """Tests only: stop exporting, empty the ring at its default size,
    zero the counters."""
    global _ring, _seq, _path, _events_recorded
    disable()
    with _lock:
        _ring = deque(maxlen=_ring_size())
        _seq = itertools.count()
        _path = None
        _events_recorded = 0
        _seen_tids.clear()


atexit.register(disable)    # flush + close the JSONL on exit


# -- recording ---------------------------------------------------------------

def set_thread_name(name: str) -> None:
    """Label the calling thread in the timeline (worker id — e.g.
    ``proc0``, ``prefetch``, ``serving``). Emitted as a Chrome ``M``
    metadata event before the thread's first exported event."""
    tid = threading.get_ident()
    _thread_names[tid] = str(name)
    if _fh is not None:
        with _lock:
            _seen_tids.discard(tid)     # re-announce


def _append(ph: str, name: str, cause, stamps, phases, counts) -> None:
    rec = (next(_seq), ph, name, threading.get_ident(), cause, stamps,
           phases, counts)
    _ring.append(rec)
    if _fh is not None:
        _write(rec)


def record(name: str, t0: float, t1: float, cause=None,
           **counts) -> None:
    """One completed span, always: a single ring append."""
    _append("X", name, cause, (t0, t1), None, counts or None)


def record_phases(name: str, stamps: Tuple[float, ...],
                  phases: Tuple[str, ...], cause=None,
                  counts: Optional[Dict[str, Any]] = None) -> None:
    """One completed step-like record, always: ``stamps[i] →
    stamps[i+1]`` is ``phases[i]``. The exporter, not the caller, fans
    it out into ``<name>/step`` and ``<name>/<phase>`` spans."""
    _append("X", name, cause, stamps, phases, counts)


def record_request(name: str, rid: int, stamps: Tuple[Any, ...],
                   counts: Dict[str, Any]) -> None:
    """One finished request, always: ``stamps`` are ``(t_submit,
    t_admit, t_first, t_last, t_done)``, ``None`` where it never got
    that far. The exporter makes its async track of it."""
    _append(REQUEST, name, rid, stamps, None, counts)


def add_span(name: str, t0: float, t1: float,
             args: Optional[Dict[str, Any]] = None) -> None:
    """A completed span from explicit :func:`now` timestamps, recorded
    only while :func:`enabled` (diagnostics off the measured paths)."""
    if not _enabled:        # the off path: one branch, no allocation
        return
    _append("X", name, None, (t0, t1), None, args or None)


def counter(name: str, values: Dict[str, Any],
            t: Optional[float] = None) -> None:
    """One sample on a Perfetto counter track (Chrome ``C`` event):
    ``values`` maps series name → number, so e.g. per-layer gradient
    norms render as stacked counter series alongside the span
    timeline. Same off-path contract as :func:`add_span`."""
    if not _enabled:
        return
    t = now() if t is None else t
    _append("C", name, None, (t, t), None,
            {k: float(v) for k, v in values.items()})


def instant(name: str, args: Optional[Dict[str, Any]] = None) -> None:
    """A point-in-time marker (Chrome ``i`` event), while enabled."""
    if not _enabled:
        return
    t = now()
    _append("i", name, None, (t, t), None, args or None)


class _NullSpan:
    """Shared no-op context manager — the disabled :func:`span` path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "t0")

    def __init__(self, name, args):
        self.name = name
        self.args = args

    def __enter__(self):
        self.t0 = now()
        return self

    def __exit__(self, *exc):
        add_span(self.name, self.t0, now(), self.args)
        return False


def span(name: str, args: Optional[Dict[str, Any]] = None):
    """``with obs.span("fit/step"): ...`` — nested spans build the
    timeline; when not :func:`enabled` this returns a shared no-op
    context manager (one branch, nothing allocated per call)."""
    if not _enabled:
        return _NULL_SPAN
    return _Span(name, args)


# -- the exporter ------------------------------------------------------------

def _us(t: float) -> float:
    return round(t * 1e6, 3)


def expand(rec) -> List[Dict[str, Any]]:
    """The Chrome events of one record (the one place a record turns
    into event dicts)."""
    _, ph, name, tid, cause, stamps, phases, counts = rec
    base = {"pid": os.getpid(), "tid": tid}
    args = dict(counts) if counts else {}
    if cause is not None and ph != REQUEST:
        args["cause"] = cause
    t0, t1 = stamps[0], stamps[-1]
    if ph == "X":
        ev = {"ph": "X", "name": name + "/step" if phases else name,
              "ts": _us(t0), "dur": _us(t1 - t0), **base}
        if args:
            ev["args"] = args
        out = [ev]
        for phase, a, b in zip(phases or (), stamps, stamps[1:]):
            out.append({"ph": "X", "name": f"{name}/{phase}",
                        "ts": _us(a), "dur": _us(b - a), **base})
        return out
    if ph == REQUEST:
        return _expand_request(name, cause, stamps, args, base)
    ev = {"ph": ph, "name": name, "ts": _us(t0), **base}
    if ph == "i":
        ev["s"] = "t"
    if args:
        ev["args"] = args
    return [ev]


def _expand_request(name, rid, stamps, args, base):
    """A request's async track (Chrome nestable async ``b``/``e``
    pairs sharing ``id``): a request's life overlaps other requests on
    the same worker thread — complete-span (``X``) nesting by interval
    containment would interleave them into garbage, while async tracks
    render one lane per ``id``."""
    t_submit, t_admit, t_first, _, t_done = stamps
    base = {"cat": "request", "id": format(int(rid), "x"), **base}
    out = []

    def pair(nm, a, b, a_args=None):
        ev = {"ph": "b", "name": nm, "ts": _us(a), **base}
        if a_args:
            ev["args"] = a_args
        out.extend([ev, {"ph": "e", "name": nm, "ts": _us(b), **base}])

    pair(name, t_submit, t_done, args)
    if t_admit is not None:
        pair(name + "/queue_wait", t_submit, t_admit)
        if t_first is not None:
            pair(name + "/prefill", t_admit, t_first)
            pair(name + "/decode_steps", t_first, t_done,
                 {"tokens": args.get("tokens")})
    return out


def _write(rec) -> None:
    """Expand one record into the open file. Called by the recording
    thread itself, so the thread's label is the caller's."""
    global _events_recorded
    evs = expand(rec)
    tid = rec[3]
    with _lock:
        if _fh is None:
            return
        _events_recorded += len(evs)
        if tid not in _seen_tids:
            _seen_tids.add(tid)
            label = _thread_names.get(tid) or \
                threading.current_thread().name
            evs.insert(0, {"ph": "M", "name": "thread_name",
                           "pid": os.getpid(), "tid": tid,
                           "args": {"name": label}})
        _fh.write("".join(json.dumps(e, separators=(",", ":")) + ",\n"
                          for e in evs))


# -- inspection --------------------------------------------------------------

def events_recorded() -> int:
    """Chrome events the exporter has written since the last reset:
    0 for as long as nothing asked for export — the nothing-built-
    when-off assertion anchor."""
    return _events_recorded


def dropped() -> int:
    """Records the ring has overwritten since the last reset."""
    ring = _ring
    return ring[0][0] if ring else 0


def records(since: Optional[float] = None) -> List[Record]:
    """The ring's records that ended at or after ``since`` (all of
    them without it), oldest first. Raises :class:`LookupError` when
    records were overwritten and the oldest survivor ended after
    ``since``: that window's start is gone, and a reader must not
    shorten it silently."""
    recs = list(_ring)
    if since is None:
        return [Record._make(r) for r in recs]
    if recs and recs[0][0] > 0 and recs[0][5][-1] > since:
        raise LookupError(
            f"the record ring (DL4J_TPU_TRACE_RING={_ring.maxlen}) "
            f"overwrote {recs[0][0]} records, among them the start of "
            f"the window asked for ({recs[0][5][-1] - since:.3f} s "
            "before its oldest record)")
    return [Record._make(r) for r in recs if r[5][-1] >= since]


def events(last: Optional[int] = None) -> List[Dict[str, Any]]:
    """The ring's tail as Chrome events (most recent ``last``, or
    all), expanded on demand."""
    recs = list(_ring)
    if last:            # a record expands into at least one event
        recs = recs[-last:]
    out = [ev for rec in recs for ev in expand(rec)]
    return out[-last:] if last else out


def trace_path() -> Optional[str]:
    return _path


def flush() -> None:
    with _lock:
        if _fh is not None:
            _fh.flush()


def read_trace(path: str) -> List[Dict[str, Any]]:
    """Parse a trace JSONL written by this module (or any Chrome-trace
    JSON array file) back into a list of event dicts."""
    out: List[Dict[str, Any]] = []
    with open(path) as f:
        text = f.read()
    stripped = text.strip()
    if stripped.startswith("[") and stripped.endswith("]"):
        try:                        # complete JSON array / traceEvents
            doc = json.loads(stripped)
            return doc.get("traceEvents", doc) \
                if isinstance(doc, dict) else doc
        except ValueError:
            pass
    for line in text.splitlines():
        line = line.strip().rstrip(",")
        if not line or line in ("[", "]"):
            continue
        try:
            out.append(json.loads(line))
        except ValueError:
            continue                # partial last line of a live file
    return out

"""Retrace sentry — trace/compile accounting for every jitted hot path.

TPU-native necessity with no reference equivalent (the reference's
eager kernels never compile): on XLA every distinct argument signature
(pytree structure + leaf shapes/dtypes) traced through a ``jax.jit``
entry point costs a full recompile. A retrace storm — e.g. an
unbucketed sequence length slipping past ``BucketedSequenceIterator``,
or a serving queue fed raw request sizes — degrades throughput
silently: every "step" is really a compile.

:func:`jit` is a drop-in for ``jax.jit`` that counts distinct traced
avals per function, records compile wall-time, and warns (or raises
under :func:`strict` / ``DL4J_TPU_RETRACE_STRICT``) once the number of
UNPLANNED signatures exceeds the budget (``DL4J_TPU_RETRACE_BUDGET``).
Shapes registered ahead of traffic through :meth:`SentryJit.warmup`
(see ``perf/warmup.py``) are *planned* and never count against the
budget — the budget meters surprises, not declared buckets.

Metrics surface through :func:`stats` (consumed by
``train.stats.StatsListener``, ``bench.py`` and
``tools/perf_dossier.py``).

**Compile lifecycle records.** :func:`install_compile_listener`
(called at package import) listens to JAX's own duration events and
appends each as a ``compile/<phase>`` record to the ``obs.trace`` ring
(``jaxpr_trace``, ``jaxpr_to_mlir``, ``backend_compile``, and on a
persistent-cache hit ``cache_retrieval``, which lies inside
``backend_compile``), caused by the sentried function on the compiling
thread's stack or by ``eager`` when there is none (``jnp.stack`` on
host arrays, a weights' maker). A nested jit's tracing is reported by
JAX inside its caller's: a reader takes the union of the records'
intervals, and :attr:`FunctionStats.phase_s` (plain sums, nested ones
counted twice) says where one function's ``compile_time_s`` went:
tracing, lowering, or compiling/loading. What a traced body decides at
trace time it can say there (:func:`note_traced`: which form of an
expert layer a program holds): the counts ride on the
``compile/jaxpr_trace`` record of the sentried function being traced.

**A key that needs no trace.** ``jit(fn, name=..., identity=...)``
takes a callable of the entry point's owner that returns, as plain
data, everything the traced program depends on that is not an
argument (``None``, or ``aot_store.CannotSay`` raised: "I cannot
say", and nothing here changes).
With one, a new signature is first looked up in the compile store's
object plane (``perf/aot_store.py``) by a key computed WITHOUT
tracing; on a hit the stored executable is loaded straight into the
table :meth:`SentryJit.warmup` fills, and the program is neither
traced nor lowered (``store_hits``; ``traces`` stays 0). On a miss it
is lowered and compiled through the AOT path, serialized and put
(``store_misses``).
"""
from __future__ import annotations

import contextlib
import functools
import logging
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

_log = logging.getLogger("deeplearning4j_tpu.perf")

_LOCK = threading.RLock()
# weakrefs: stats live exactly as long as their SentryJit (and thus the
# net) does — a long-running server constructing models repeatedly must
# not accumulate dead ledgers. Call under _LOCK.
_REGISTRY: List["weakref.ref[FunctionStats]"] = []


def _live_stats() -> List["FunctionStats"]:
    out = [s for s in (r() for r in _REGISTRY) if s is not None]
    if len(out) != len(_REGISTRY):
        _REGISTRY[:] = [r for r in _REGISTRY if r() is not None]
    return out

#: JAX's duration events → the phase name of the record made of each
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_tls = threading.local()    # .owner: FunctionStats compiling up-stack
_listening = False


def _on_duration(event: str, duration: float, **kw) -> None:
    """One ``compile/<phase>`` ring record a JAX duration event; JAX
    calls this on the compiling thread as the phase ends."""
    phase = COMPILE_PHASES.get(event)
    if phase is None:
        return
    from deeplearning4j_tpu.obs import trace
    owner = getattr(_tls, "owner", None)
    t1 = trace.now()
    said = {}
    if (phase == "jaxpr_trace" and owner is not None
            and kw.get("fun_name") == owner.fun):
        # the sentried function's own trace ends (nested jits' ended
        # before it): what its body noted goes on this record
        said, _tls.notes = _tls.notes, {}
    trace.record("compile/" + phase, t1 - duration, t1,
                 owner.name if owner is not None else "eager",
                 fun=kw.get("fun_name"), **said)
    if owner is not None:
        with _LOCK:
            owner.phase_s[phase] += duration


def note_traced(tally: str, **facts) -> None:
    """Called by a body WHILE a sentried function traces it: adds one
    to ``tally`` and sets ``facts`` among the counts of that trace's
    ``compile/jaxpr_trace`` record. Outside a sentried call (eager, a
    plain ``jax.jit``, :meth:`SentryJit.lower`) nothing is kept."""
    notes = getattr(_tls, "notes", None)
    if notes is not None:
        notes[tally] = notes.get(tally, 0) + 1
        notes.update(facts)


def install_compile_listener() -> None:
    """Register :func:`_on_duration` with ``jax.monitoring`` (once)."""
    global _listening
    if _listening:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _listening = True


@contextlib.contextmanager
def _compiling(stats: "FunctionStats"):
    """Name ``stats`` as the cause of what this thread compiles
    meanwhile (the outermost sentried function wins a nested call)."""
    prev = getattr(_tls, "owner", None)
    if prev is None:
        _tls.owner, _tls.notes = stats, {}
    try:
        yield
    finally:
        _tls.owner = prev
        if prev is None:
            _tls.notes = None


# strict()/budget() context overrides (None -> read the env flags)
_STRICT_OVERRIDE: Optional[bool] = None
_BUDGET_OVERRIDE: Optional[int] = None


class RetraceBudgetExceeded(RuntimeError):
    """A jitted entry point traced more distinct unplanned shapes than
    its retrace budget allows (retrace storm)."""


def _flag(name):
    from deeplearning4j_tpu import environment
    return environment.get_flag(name)


def _is_strict() -> bool:
    if _STRICT_OVERRIDE is not None:
        return _STRICT_OVERRIDE
    return bool(_flag("DL4J_TPU_RETRACE_STRICT"))


def _default_budget() -> int:
    if _BUDGET_OVERRIDE is not None:
        return _BUDGET_OVERRIDE
    return int(_flag("DL4J_TPU_RETRACE_BUDGET"))


def signature(tree) -> tuple:
    """Hashable aval signature of an argument pytree: treedef + per-leaf
    (shape, dtype). Works on concrete arrays, tracers, and
    ``ShapeDtypeStruct``s alike — the same triple ``jax.jit`` keys its
    trace cache on (sans weak-type/sharding, which never differ along
    our entry points' call paths)."""
    import jax
    leaves, treedef = jax.tree.flatten(tree)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is not None:
            sig.append((tuple(shape), str(getattr(leaf, "dtype", "?"))))
        else:                       # python scalar / static-ish leaf
            sig.append(("py", type(leaf).__name__))
    return (treedef, tuple(sig))


class FunctionStats:
    """Per-entry-point counters (one per SentryJit instance; sharing a
    name across instances is fine — :func:`stats` merges by name)."""

    def __init__(self, name: str, budget: Optional[int]):
        self.name = name
        self.fun = name               # the traced function's own name
        self.budget = budget          # None -> global flag/override
        self.traces = 0               # total tracings (incl. planned)
        self.compiles = 0             # compiles observed on live calls
        self.warmed = 0               # compiles done ahead of traffic
        self.aot_hits = 0             # live calls served by a warmed
                                      # executable (zero-compile proof)
        self.store_hits = 0           # executables loaded by their key,
                                      # neither traced nor lowered
        self.store_misses = 0         # keyed programs compiled and put
        self.compile_time_s = 0.0     # wall-time spent compiling
        # seconds JAX reported per compile phase while this function
        # was on the compiling thread's stack
        self.phase_s = dict.fromkeys(COMPILE_PHASES.values(), 0.0)
        self.signatures: set = set()  # every distinct traced aval sig
        self.planned: set = set()     # declared via warmup()

    # -- accounting -----------------------------------------------------
    def note_plan(self, sig):
        with _LOCK:
            self.planned.add(sig)

    def note_trace(self, sig):
        with _LOCK:
            self.traces += 1
            self.signatures.add(sig)
            unplanned = len(self.signatures - self.planned)
            budget = (self.budget if self.budget is not None
                      else _default_budget())
            over = unplanned > budget
        if over:
            msg = (f"retrace sentry: {self.name!r} traced {unplanned} "
                   f"distinct unplanned shapes (budget {budget}) — "
                   "likely a retrace storm; bucket the offending "
                   "shapes (BucketedSequenceIterator / ParallelInference "
                   "buckets) or declare them via warmup()")
            if _is_strict():
                raise RetraceBudgetExceeded(msg)
            _log.warning(msg)

    def unplanned(self) -> int:
        with _LOCK:
            return len(self.signatures - self.planned)

    def snapshot(self) -> Dict[str, Any]:
        with _LOCK:
            return {
                "traces": self.traces,
                "distinct_shapes": len(self.signatures),
                "unplanned_shapes": len(self.signatures - self.planned),
                "planned_shapes": len(self.planned),
                "compiles": self.compiles,
                "warmed": self.warmed,
                "aot_hits": self.aot_hits,
                "store_hits": self.store_hits,
                "store_misses": self.store_misses,
                "compile_time_s": self.compile_time_s,
                **{f"{k}_s": v for k, v in self.phase_s.items()},
            }


class SentryJit:
    """``jax.jit`` plus trace accounting and AOT warmup.

    Un-warmed calls dispatch through the wrapped jit exactly as
    before; the only interception is a counter bump at TRACE time (the
    wrapped python fn body runs once per cache miss), so their
    steady-state dispatch overhead is zero. ``warmup(*args)``
    lowers+compiles from (possibly abstract) arguments and KEEPS the
    compiled executable: on this jax the AOT ``.lower().compile()``
    path does not populate jit's own dispatch cache (only the trace
    cache), so a warmed signature is routed straight to its stored
    executable — the first real call on it neither traces nor compiles
    (``aot_hits`` in the stats is the proof).

    With an ``identity`` (module doc) and a persistent cache
    directory, both ``warmup`` and the first call on a new signature
    first ask the compile store for the executable by its key, and
    fill the same table from the stored bytes.
    """

    def __init__(self, fn, name: Optional[str] = None,
                 budget: Optional[int] = None, identity=None,
                 **jit_kwargs):
        import jax
        self._fn = fn
        self._aot: Dict[tuple, Any] = {}   # sig -> Compiled
        self._identity = identity
        self._jit_kwargs = jit_kwargs
        self.name = name or getattr(fn, "__name__", "jit_fn")
        self.stats = FunctionStats(self.name, budget)
        stats = self.stats

        @functools.wraps(fn)
        def counted(*args, **kwargs):
            stats.note_trace(signature((args, kwargs)))
            return fn(*args, **kwargs)

        stats.fun = counted.__name__    # what JAX calls its trace
        self._jitted = jax.jit(counted, **jit_kwargs)
        with _LOCK:
            _REGISTRY.append(weakref.ref(stats))

    def __call__(self, *args, **kwargs):
        st = self.stats
        if self._aot or self._identity is not None:
            sig = signature((args, kwargs))
            compiled = self._aot.get(sig)
            if compiled is None and self._identity is not None:
                compiled = self._by_key(sig, args, kwargs)
            if compiled is not None:
                try:
                    out = compiled(*args, **kwargs)
                except (TypeError, ValueError):
                    # pre-execution arg rejection (layout/sharding
                    # drifted from the warmed executable): fall
                    # through to jit, whose trace/compile the counters
                    # then see. Runtime failures (OOM, debug_nans)
                    # must propagate — donated buffers are gone and
                    # the crash handlers key on the original error
                    pass
                else:
                    with _LOCK:
                        st.aot_hits += 1
                    return out
        before = st.traces
        t0 = time.perf_counter()
        with _compiling(st):
            out = self._jitted(*args, **kwargs)
        if st.traces != before:     # this call traced -> it compiled
            dt = time.perf_counter() - t0
            with _LOCK:
                st.compiles += 1
                st.compile_time_s += dt
        return out

    def warmup(self, *args, **kwargs):
        """AOT-compile for the given argument signature (concrete
        arrays and ``ShapeDtypeStruct``s mix freely), keep the
        executable for dispatch, and mark the signature PLANNED.
        Idempotent per signature. Returns the seconds it took to have
        the executable, compiled or loaded by its key (0.0 when the
        signature was already traced or loaded)."""
        st = self.stats
        sig = signature((args, kwargs))
        st.note_plan(sig)
        with _LOCK:
            if sig in st.signatures or (self._identity is not None
                                        and sig in self._aot):
                return 0.0          # already traced/compiled/loaded
        t0 = time.perf_counter()
        keyed = self._keyed(args, kwargs)
        if keyed is None:
            with _compiling(st):
                self._aot[sig] = self._jitted.lower(*args,
                                                    **kwargs).compile()
        else:
            self._aot[sig] = self._load_or_put(keyed, args, kwargs)
        dt = time.perf_counter() - t0
        with _LOCK:
            st.warmed += 1
            st.compile_time_s += dt
        return dt

    # -- the compile store ------------------------------------------------
    def _keyed(self, args, kwargs):
        """``(store, key)`` of this signature's executable, or None:
        no identity, an owner that cannot say, or no cache directory
        (then the entry point takes the path it always took)."""
        if self._identity is None:
            return None
        from deeplearning4j_tpu.perf import aot_store
        store = aot_store.store()
        if store is None:
            return None
        try:
            said = self._identity()
            if said is not None:
                return store, aot_store.program_key(
                    self.name, self._jit_kwargs, args, kwargs, said)
        except aot_store.CannotSay:
            pass
        self._identity = None       # the owner cannot say: for good
        return None

    def _load_or_put(self, keyed, args, kwargs):
        """The executable under ``keyed``: loaded, or lowered and
        compiled as always and then put. A load makes the records a
        persistent-cache hit makes (``compile/backend_compile`` around
        ``compile/cache_retrieval``) and counts as one."""
        from deeplearning4j_tpu.obs import trace
        from deeplearning4j_tpu.perf import aot_store, compile_cache

        store, key = keyed
        st = self.stats
        t0 = trace.now()
        compiled = aot_store.load(store, key, args, kwargs)
        if compiled is not None:
            t1 = trace.now()
            trace.record("compile/cache_retrieval", t0, t1, st.name,
                         fun=st.name)
            trace.record("compile/backend_compile", t0, trace.now(),
                         st.name, fun=st.name)
            compile_cache.note_store_load()
            with _LOCK:
                st.store_hits += 1
                st.phase_s["cache_retrieval"] += t1 - t0
                st.phase_s["backend_compile"] += t1 - t0
            return compiled
        with _LOCK:
            st.store_misses += 1
        with _compiling(st):
            with aot_store.no_xla_write():
                compiled = self._jitted.lower(*args, **kwargs).compile()
            if not aot_store.save(store, key, compiled):
                # not storable: leave it in the XLA plane as before
                self._identity = None
                compiled = self._jitted.lower(*args, **kwargs).compile()
        return compiled

    def _by_key(self, sig, args, kwargs):
        """A live call on a signature nothing warmed: the executable
        by its key, kept for this signature's later calls; None where
        the entry point has no key."""
        keyed = self._keyed(args, kwargs)
        if keyed is None:
            return None
        st = self.stats
        before = st.traces
        t0 = time.perf_counter()
        compiled = self._aot[sig] = self._load_or_put(keyed, args, kwargs)
        with _LOCK:
            st.compile_time_s += time.perf_counter() - t0
            if st.traces != before:
                st.compiles += 1
        return compiled

    # AOT inspection passthroughs
    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    @property
    def __wrapped__(self):
        return self._fn


def jit(fn, *, name: Optional[str] = None,
        budget: Optional[int] = None, identity=None,
        **jit_kwargs) -> SentryJit:
    """Drop-in ``jax.jit`` with retrace accounting; ``identity`` lets
    a warm start load the program by a key that needs no trace (see
    module doc)."""
    return SentryJit(fn, name=name, budget=budget, identity=identity,
                     **jit_kwargs)


# -- global controls --------------------------------------------------------

@contextlib.contextmanager
def strict(budget: Optional[int] = None):
    """Within the context, blowing a retrace budget RAISES
    :class:`RetraceBudgetExceeded` instead of warning; ``budget``
    optionally overrides every function's budget. The CI tier-1 fence
    runs a tiny fit under ``sentry.strict()`` so a future PR that
    introduces a retrace storm fails loudly."""
    global _STRICT_OVERRIDE, _BUDGET_OVERRIDE
    prev = (_STRICT_OVERRIDE, _BUDGET_OVERRIDE)
    _STRICT_OVERRIDE = True
    if budget is not None:
        _BUDGET_OVERRIDE = budget
    try:
        yield
    finally:
        _STRICT_OVERRIDE, _BUDGET_OVERRIDE = prev


def stats() -> Dict[str, Dict[str, Any]]:
    """Merged per-name counter snapshot for every sentried entry point
    that traced or warmed at least once."""
    with _LOCK:
        recs = [(s.name, s.snapshot()) for s in _live_stats()]
    out: Dict[str, Dict[str, Any]] = {}
    for name, snap in recs:
        if snap["traces"] == 0 and snap["warmed"] == 0 \
                and snap["store_hits"] == 0:
            continue
        if name not in out:
            out[name] = snap
        else:
            agg = out[name]
            for k, v in snap.items():
                agg[k] = agg[k] + v
    return out


def total_traces() -> int:
    """Total tracings across every sentried entry point — the
    zero-new-compiles assertion anchor for warmup tests."""
    with _LOCK:
        return sum(s.traces for s in _live_stats())


def total_compile_time_s() -> float:
    with _LOCK:
        return sum(s.compile_time_s for s in _live_stats())


def reset() -> None:
    """Zero every counter and forget dead entries (stats of live
    SentryJit instances are zeroed in place — their jit caches and
    warmed executables survive)."""
    with _LOCK:
        for s in _live_stats():
            s.traces = s.compiles = s.warmed = s.aot_hits = 0
            s.store_hits = s.store_misses = 0
            s.compile_time_s = 0.0
            s.phase_s = dict.fromkeys(COMPILE_PHASES.values(), 0.0)
            s.signatures.clear()
            s.planned.clear()

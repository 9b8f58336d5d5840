"""Persistent XLA compilation cache — compiles survive the process.

Every fresh process pays full XLA compilation on the first step of
every ``(model, bucket)`` pair; on TPU a big train step is tens of
seconds. JAX ships the fix (``jax_compilation_cache_dir``: serialized
executables keyed by HLO + compile options, shared on disk) and this
module wires it into the tier-2 flag system: :func:`configure_from_env`
runs at package import, so restarts, ``ParallelWrapper`` worker
processes and ``tests/mp_harness.py`` children all reuse each other's
compiles with zero per-callsite code.

Where the cache lives, in order:

- ``JAX_COMPILATION_CACHE_DIR`` — JAX's own variable. When it is set
  the cache was placed from outside: JAX reads it itself, this module
  reports it and never sets another directory, whatever the flags
  below say.
- ``DL4J_TPU_COMPILE_STORE`` — the fenced fleet store's ``xla/`` plane
  (``compile_store.py``).
- ``DL4J_TPU_COMPILE_CACHE`` — cache dir; the default is the fixed
  path ``<checkout>/.jax_cache`` (``environment.DEFAULT_COMPILE_CACHE``,
  git-ignored), the same for every process of this checkout whether
  its platform was named or auto-detected — the path is part of what
  makes a later run hit. '' / '0' / 'off' / 'none' disables. The
  default is skipped only in a process NAMED as CPU-only (see
  :func:`configure`); setting the variable turns it on there too.

Flags (``environment.py``):

- ``DL4J_TPU_COMPILE_CACHE_MIN_BYTES`` / ``_MIN_SECS`` — eligibility
  floors (both default to "cache everything": first-request latency is
  the target, and small entries are exactly the many-bucket serving
  case).

Hit/miss counters come from ``jax.monitoring`` events and surface in
:func:`cache_stats` (consumed by ``bench.py`` and the perf dossier).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

_LOCK = threading.Lock()
_state: Dict[str, Any] = {
    "dir": None,             # active cache dir (None -> disabled)
    "store": None,           # CompileStore when routed through one
    "listeners": False,      # monitoring listeners installed
    "requests": 0,           # compile requests eligible for the cache
    "hits": 0,               # persistent-cache hits
}

_DISABLED = {"", "0", "off", "none", "false", "disabled"}

#: JAX's own variable for the cache directory — when set, the cache
#: was placed from outside and this module sets no other
JAX_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"


def _on_event(event: str, **kw) -> None:
    if event.endswith("/compilation_cache/compile_requests_use_cache"):
        with _LOCK:
            _state["requests"] += 1
    elif event.endswith("/compilation_cache/cache_hits"):
        with _LOCK:
            _state["hits"] += 1


def _install_listeners() -> None:
    if _state["listeners"]:
        return
    import jax.monitoring
    jax.monitoring.register_event_listener(_on_event)
    _state["listeners"] = True


def _cpu_named() -> bool:
    """True when the process's platform is NAMED, and as the CPU alone
    (``JAX_PLATFORMS=cpu`` or the ``jax_platforms`` config). Read from
    the config only — never ``jax.devices()``, which would initialize
    a backend at package import. A process whose platform is
    auto-detected (nothing named: the attached TPU) is not CPU-named
    and gets the default cache."""
    import jax
    names = [p.strip() for p in
             str(jax.config.jax_platforms or "").split(",") if p.strip()]
    return names == ["cpu"]


def _set_disabled() -> None:
    with _LOCK:
        _state["dir"] = None
        _state["store"] = None


def configure(cache_dir: Optional[str] = None,
              min_entry_size_bytes: Optional[int] = None,
              min_compile_time_secs: Optional[float] = None
              ) -> Optional[str]:
    """Point JAX's persistent compilation cache at ``cache_dir``
    (created if missing) and drop the eligibility floors. Arguments
    default to the ``DL4J_TPU_COMPILE_CACHE*`` flags. Returns the
    active dir, or None when disabled. Safe to call repeatedly and
    before/after backends initialize (``jax.config`` updates apply to
    subsequent compiles); never initializes a backend itself.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins over the argument and the
    flags: that directory is used as JAX read it, and
    ``jax_compilation_cache_dir`` is not updated from here — only the
    floors and the hit/miss listeners are installed.

    The DEFAULT dir does not apply to a process NAMED as CPU-only
    (:func:`_cpu_named`): jaxlib 0.9.0 reads an XLA:CPU entry back
    through its AOT loader, which logs a multi-kilobyte "machine type
    ... doesn't match ... could lead to SIGILL" error for every hit
    (measured here: examples/pretrained_zoo.py, 29 hits, 58 such
    lines). Such a process caches only when a directory is given
    explicitly (either env var, the store, or the argument)."""
    from deeplearning4j_tpu import environment
    import jax

    store = None
    external = os.environ.get(JAX_ENV_DIR, "").strip()
    if external:
        cache_dir = external
    elif cache_dir is None:
        # the content-addressed fleet store (perf/compile_store.py)
        # supersedes the flat cache dir when configured: its fenced
        # xla/ plane becomes the JAX cache dir, so a jaxlib/topology
        # change can never serve a stale executable
        from deeplearning4j_tpu.perf import compile_store
        store = compile_store.from_env()
        if store is not None:
            cache_dir = str(store.xla_dir)
        elif "DL4J_TPU_COMPILE_CACHE" in os.environ or not _cpu_named():
            cache_dir = environment.get_flag("DL4J_TPU_COMPILE_CACHE")
    if cache_dir is None or str(cache_dir).strip().lower() in _DISABLED:
        _set_disabled()
        return None
    cache_dir = os.path.expanduser(str(cache_dir))
    os.makedirs(cache_dir, exist_ok=True)
    if min_entry_size_bytes is None:
        min_entry_size_bytes = environment.get_flag(
            "DL4J_TPU_COMPILE_CACHE_MIN_BYTES")
    if min_compile_time_secs is None:
        min_compile_time_secs = environment.get_flag(
            "DL4J_TPU_COMPILE_CACHE_MIN_SECS")
    if not external:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      int(min_entry_size_bytes))
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_time_secs))
    _install_listeners()
    with _LOCK:
        _state["dir"] = cache_dir
        _state["store"] = store
    return cache_dir


def note_store_load() -> None:
    """An executable loaded from the compile store by its key
    (``perf/aot_store.py``): a compile request served without a
    compile, as a hit of the XLA plane is. (A key that is absent
    counts nothing here: the compile that follows makes its own
    request of the XLA plane.)"""
    with _LOCK:
        _state["requests"] += 1
        _state["hits"] += 1


def active_store():
    """The :class:`~deeplearning4j_tpu.perf.compile_store.CompileStore`
    the cache is routed through, or None (flat dir / disabled)."""
    return _state["store"]


def configure_from_env() -> Optional[str]:
    """Import-time entry point (called from the package ``__init__``):
    configure entirely from flags, never raise — an unwritable cache
    dir degrades to no caching, not an import error."""
    try:
        return configure()
    except OSError:
        _set_disabled()
        return None


def cache_dir() -> Optional[str]:
    return _state["dir"]


def counters() -> Dict[str, int]:
    """In-process compile-request/hit counters only — no disk walk, so
    safe on the per-iteration training hot path (``cache_stats`` walks
    the whole cache dir and belongs in once-per-run reporters)."""
    with _LOCK:
        requests, hits = _state["requests"], _state["hits"]
    return {"compile_requests": requests, "persistent_hits": hits,
            "persistent_misses": max(0, requests - hits)}


def cache_stats() -> Dict[str, Any]:
    """On-disk + in-process view of the persistent cache: entry count
    and bytes in the dir, and this process's eligible compile requests
    vs persistent hits (misses = requests - hits; a miss is a compile
    another process can now skip)."""
    d = _state["dir"]
    entries = 0
    size = 0
    if d and os.path.isdir(d):
        for root, _dirs, files in os.walk(d):
            for f in files:
                if f.endswith("-atime"):    # LRU bookkeeping, not entries
                    continue
                entries += 1
                try:
                    size += os.path.getsize(os.path.join(root, f))
                except OSError:
                    pass
    with _LOCK:
        requests, hits = _state["requests"], _state["hits"]
        store = _state["store"]
    out = {
        "dir": d,
        "enabled": d is not None,
        "entries": entries,
        "bytes": size,
        "compile_requests": requests,
        "persistent_hits": hits,
        "persistent_misses": max(0, requests - hits),
    }
    if store is not None:
        out["store_fence"] = store.fence
        out["store"] = store.counters()
    return out


def reset_counters() -> None:
    with _LOCK:
        _state["requests"] = _state["hits"] = 0

"""Executables by a key that needs no trace — the compile store's
object plane holding its first executables.

A warm start used to trace every program in Python and lower it to
MLIR only to compute the key under which the finished executable
already lay in the persistent cache. An entry point whose OWNER can
say what the traced program depends on (``sentry.jit(fn, name=...,
identity=...)``) is looked up here instead, before anything is traced:
:func:`program_key` hashes everything a trace would have read, and
:func:`load` hands ``SentryJit`` a ``jax.stages.Compiled`` made from
the stored bytes (``jax.experimental.serialize_executable``).

**The key** is the store's fence (jaxlib, named platform) and

- a digest of every source file of the installed package
  (:func:`package_digest`: any edit to the package is another key);
- the versions of jax, numpy, optax and Python, the backend's
  ``platform`` / ``platform_version`` (libtpu's build on the chip), the
  default device's kind and id, the process count;
- the entry point's name and its ``jit`` keywords;
- the arguments' tree and, a leaf, shape, dtype, weak type and
  sharding with its device ids (:func:`describe_args`);
- every registered ``environment`` flag's value, the kernel gates, the
  ``jax.config`` values a trace reads (:data:`CONFIG_NAMES`),
  ``XLA_FLAGS`` and ``LIBTPU_INIT_ARGS``;
- the owner's ``identity()``, made with :func:`describe`: plain data
  only. An owner that holds code from outside the digested package (a
  user's layer class, a closure) cannot say what its program is:
  :func:`describe` raises :class:`CannotSay`, which ``SentryJit`` takes
  as it takes ``None``, and the entry point takes the path it always
  took.

**Where the bytes lie.** In the object plane of the fleet store when
the cache is routed through one (``DL4J_TPU_COMPILE_STORE``), else in
a store of its own rooted at ``<cache dir>/aot``: whatever keeps the
persistent cache between runs keeps the artifacts, and turning the
cache off turns them off. A program that is put here leaves no entry
in the XLA plane (:func:`no_xla_write`). An artifact is a pickle of
the serialized executable, its two trees and its device ids, packed as
JAX packs its own cache entries; the artifacts keep to
:func:`max_bytes` by least-recent load.

An artifact that does not load (CRC, unpickling, the backend's
refusal, a tree that is not the arguments') is quarantined and
reported as a miss: set-up never fails for the store.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import logging
import os
import pickle
import sys
import threading
import zlib
from pathlib import Path
from typing import Any, Dict

try:
    import zstandard
except ImportError:         # pragma: no cover - installed with jax here
    zstandard = None

_log = logging.getLogger("deeplearning4j_tpu.perf")
_ZSTD_MAGIC = b"\x28\xb5\x2f\xfd"

PACKAGE = "deeplearning4j_tpu"
#: sub-directory of the persistent cache's directory
SUBDIR = "aot"
#: the artifacts' byte limit where ``jax_compilation_cache_max_size``
#: sets none for the XLA plane
DEFAULT_MAX_BYTES = 2 << 30
#: ``jax.config`` values a trace or a lowering reads
CONFIG_NAMES = (
    "jax_default_matmul_precision", "jax_enable_x64",
    "jax_default_prng_impl", "jax_threefry_partitionable",
    "jax_numpy_dtype_promotion", "jax_numpy_rank_promotion",
    "jax_debug_nans", "jax_debug_infs", "jax_disable_jit")
#: environment variables the compiler reads
COMPILER_ENV = ("XLA_FLAGS", "LIBTPU_INIT_ARGS")

_LOCK = threading.Lock()
_digests: Dict[str, str] = {}
_stores: Dict[str, Any] = {}


class CannotSay(Exception):
    """An owner's program depends on something :func:`describe` cannot
    put into words: code or data from outside the digested package."""


# -- the owner's words ------------------------------------------------------

def _in_package(obj) -> bool:
    mod = getattr(obj, "__module__", None) or ""
    return mod == PACKAGE or mod.startswith(PACKAGE + ".")


def _qualname(obj) -> str:
    return f"{obj.__module__}.{obj.__qualname__}"


def describe(obj, _seen=None):
    """``obj`` as JSON-able data that says everything a trace could
    read of it. Plain data as itself; an instance of a package class
    as its qualified class name and its public attributes (or its
    dataclass fields); a package function or class as its qualified
    name. Anything else (an instance of a class defined outside the
    package, a closure, a device array) raises :class:`CannotSay`."""
    import numpy as np

    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        return repr(obj)            # nan and inf are not JSON
    if isinstance(obj, (np.generic, np.dtype)):
        return {"@np": type(obj).__name__, "value": str(obj)}
    if isinstance(obj, np.ndarray):
        return {"@ndarray": str(obj.dtype), "shape": list(obj.shape),
                "sha256": hashlib.sha256(
                    np.ascontiguousarray(obj).tobytes()).hexdigest()}
    seen = _seen if _seen is not None else set()
    if id(obj) in seen:
        raise CannotSay(f"a cycle through {type(obj).__name__}")
    seen = seen | {id(obj)}
    if isinstance(obj, (list, tuple)):
        return [describe(v, seen) for v in obj]
    if isinstance(obj, dict):
        return {"@dict": sorted(([describe(k, seen), describe(v, seen)]
                                 for k, v in obj.items()), key=repr)}
    if isinstance(obj, type) or callable(obj) and hasattr(
            obj, "__qualname__") and not hasattr(obj, "__self__"):
        try:                        # jnp.float32 and its like
            return {"@np": "dtype", "value": str(np.dtype(obj))}
        except TypeError:
            pass
        if not _in_package(obj) or "<locals>" in obj.__qualname__ \
                or getattr(obj, "__closure__", None):
            raise CannotSay(f"code from outside the package: {obj!r}")
        return {"@code": _qualname(obj)}
    cls = type(obj)
    if not _in_package(cls) or "<locals>" in cls.__qualname__:
        raise CannotSay(f"an instance of {cls.__module__}."
                        f"{cls.__qualname__}, defined outside the "
                        "package")
    if dataclasses.is_dataclass(obj):
        fields = {f.name: getattr(obj, f.name)
                  for f in dataclasses.fields(obj)}
    elif hasattr(obj, "__dict__"):
        fields = {k: v for k, v in vars(obj).items()
                  if not k.startswith("_")}
    else:
        raise CannotSay(f"{_qualname(cls)} keeps its state out of sight")
    return {"@class": _qualname(cls),
            **{k: describe(v, seen) for k, v in sorted(fields.items())}}


# -- what else a trace reads ------------------------------------------------

def package_digest(root=None) -> str:
    """sha256 over every file of the installed package, by relative
    path and bytes (compiled Python left out), once a process."""
    if root is None:
        root = Path(sys.modules[PACKAGE].__file__).resolve().parent
    root = Path(root)
    with _LOCK:
        got = _digests.get(str(root))
    if got is not None:
        return got
    h = hashlib.sha256()
    for path in sorted(p for p in root.rglob("*") if p.is_file()
                       and "__pycache__" not in p.parts
                       and p.suffix != ".pyc"):
        h.update(path.relative_to(root).as_posix().encode() + b"\0")
        data = path.read_bytes()
        h.update(str(len(data)).encode() + b"\0")
        h.update(data)
    with _LOCK:
        _digests[str(root)] = h.hexdigest()
    return h.hexdigest()


def environment_parts() -> dict:
    """Flags, gates, config values and compiler variables, as now."""
    import jax
    from deeplearning4j_tpu import environment
    from deeplearning4j_tpu.ops import kernel_registry

    gates = sorted({e["gate"] for e in
                    kernel_registry.KERNEL_REGISTRY.values()})
    out = {
        "flags": {n: repr(environment.get_flag(n))
                  for n in sorted(environment.FLAGS)},
        "gates": {g: kernel_registry.gate_active(g) for g in gates},
        "config": {n: repr(getattr(jax.config, n, None))
                   for n in CONFIG_NAMES},
        "env": {n: os.environ.get(n) for n in COMPILER_ENV},
    }
    try:        # whatever else jit keys its own trace cache on
        from jax._src import config as jax_config
        out["trace_context"] = repr(jax_config.trace_context())
    except Exception:           # pragma: no cover - jax moved it
        pass
    return out


def backend_parts() -> dict:
    import jax
    import numpy
    import optax

    dev = jax.config.jax_default_device or jax.devices()[0]
    client = dev.client
    return {"jax": jax.__version__, "numpy": numpy.__version__,
            "optax": optax.__version__,
            "python": list(sys.version_info[:3]),
            "platform": client.platform,
            "platform_version": client.platform_version,
            "device_kind": dev.device_kind, "device": dev.id,
            "devices": jax.device_count(),
            "processes": jax.process_count()}


def _describe_sharding(leaf) -> Any:
    """A leaf's placement as a lowering sees it: ``None`` for one
    that is left to the default device (an uncommitted array, a
    ``ShapeDtypeStruct`` without a sharding, a Python scalar)."""
    sharding = getattr(leaf, "sharding", None)
    if sharding is None or not getattr(leaf, "_committed", True):
        return None
    ids = getattr(sharding, "_device_assignment", None)
    if ids is None:
        ids = sorted(sharding.device_set, key=lambda d: d.id)
    return [repr(sharding), [d.id for d in ids]]


def describe_args(args, kwargs) -> dict:
    """``sentry.signature`` for a key on disk: the tree as text, and
    sharding and weak type beside each leaf's shape and dtype."""
    import jax

    leaves, treedef = jax.tree.flatten((args, kwargs))
    rows = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is None:           # a Python scalar: weakly typed
            rows.append(["py", type(leaf).__name__])
            continue
        weak = getattr(leaf, "weak_type", None)
        if weak is None:
            weak = bool(getattr(getattr(leaf, "aval", None),
                                "weak_type", False))
        rows.append([list(shape), str(getattr(leaf, "dtype", "?")),
                     bool(weak), _describe_sharding(leaf)])
    return {"tree": str(treedef), "leaves": rows}


def program_key(name: str, jit_kwargs: dict, args, kwargs,
                identity, package_root=None) -> str:
    """The fingerprint under which the program's executable lies."""
    from deeplearning4j_tpu.perf.compile_store import program_fingerprint
    return program_fingerprint(
        package=package_digest(package_root),
        backend=backend_parts(), environment=environment_parts(),
        name=name, jit=describe(jit_kwargs),
        args=describe_args(args, kwargs), identity=identity)


# -- the store --------------------------------------------------------------

def max_bytes() -> int:
    """The artifacts' own byte limit: the number the XLA plane was
    given (``jax_compilation_cache_max_size``), else
    :data:`DEFAULT_MAX_BYTES`."""
    import jax
    size = int(getattr(jax.config, "jax_compilation_cache_max_size", -1))
    return size if size > 0 else DEFAULT_MAX_BYTES


def store():
    """The store the artifacts lie in, or None with the persistent
    cache off (a CPU-named process without a named directory has
    none)."""
    from deeplearning4j_tpu.perf import compile_cache, compile_store
    routed = compile_cache.active_store()
    if routed is not None:
        routed.max_bytes = max_bytes()
        return routed
    cache_dir = compile_cache.cache_dir()
    if cache_dir is None:
        return None
    with _LOCK:
        st = _stores.get(cache_dir)
    if st is None:
        try:
            st = compile_store.CompileStore(
                Path(cache_dir) / SUBDIR, max_bytes=max_bytes())
        except OSError as e:
            _log.warning("no executable store under %s: %s",
                         cache_dir, e)
            return None
        with _LOCK:
            st = _stores.setdefault(cache_dir, st)
    return st


@contextlib.contextmanager
def no_xla_write():
    """Compile without leaving an entry in the XLA plane: the
    executable is about to be put as an artifact, and no executable
    lies on disk twice. The plane is still READ (an entry a tree
    without this module wrote is a hit, and saves the compile)."""
    # the floor below which JAX does not write an entry, for this
    # thread, while the compile runs
    from jax._src import config as jax_config
    with jax_config.persistent_cache_min_compile_time_secs(float("inf")):
        yield


def _compress(blob: bytes) -> bytes:
    """As JAX packs its own cache entries: zstandard where the module
    is installed, else zlib. A TPU executable packs to about a third,
    so three times as many programs fit the byte limit."""
    if zstandard is not None:
        return zstandard.ZstdCompressor().compress(blob)
    return zlib.compress(blob, 1)


def _decompress(blob: bytes) -> bytes:
    if blob[:4] == _ZSTD_MAGIC:
        return zstandard.ZstdDecompressor().decompress(blob)
    return zlib.decompress(blob)


def save(st, key: str, compiled) -> bool:
    """Serialize ``compiled`` and put it under ``key``. False where
    the executable cannot be serialized (constants closed over by the
    traced function) or the store cannot be written: the caller then
    compiles through the XLA plane as it always did."""
    from jax.experimental import serialize_executable
    try:
        blob, in_tree, out_tree = serialize_executable.serialize(compiled)
        devices = [d.id for d in
                   compiled.runtime_executable().local_devices()]
        st.put(key, _compress(pickle.dumps(
            {"executable": blob, "in_tree": in_tree,
             "out_tree": out_tree, "devices": devices},
            protocol=pickle.HIGHEST_PROTOCOL)))
        return True
    except Exception as e:
        _log.warning("executable not stored (%s: %s)",
                     type(e).__name__, e)
        return False


def load(st, key: str, args, kwargs):
    """The ``Compiled`` stored under ``key``, loaded onto the devices
    it was compiled for, or None: absent, or unloadable and then
    quarantined."""
    import jax
    from jax.experimental import serialize_executable

    payload = st.get(key)
    if payload is None:
        return None
    try:
        entry = pickle.loads(_decompress(payload))
        if entry["in_tree"] != jax.tree.structure((args, kwargs)):
            raise ValueError("the stored tree is not the arguments'")
        by_id = {d.id: d for d in jax.devices()}
        devices = [by_id[i] for i in entry["devices"]]
        return serialize_executable.deserialize_and_load(
            entry["executable"], entry["in_tree"], entry["out_tree"],
            backend=devices[0].client, execution_devices=devices)
    except Exception as e:
        _log.warning("stored executable %s does not load (%s: %s); "
                     "quarantined, compiling", key[:12],
                     type(e).__name__, e)
        st.quarantine(key, f"unloadable: {type(e).__name__}")
        return None


# -- a net's train programs -------------------------------------------------

def net_identity(net):
    """What a net's traced train programs depend on beside their
    arguments (``MultiLayerNetwork`` / ``ComputationGraph``
    ``train_loop``): the net's class, its configuration field by field
    (layers or nodes, updater and its schedule, the compute dtype; NOT
    the seed, which reaches a program only as its ``rng`` arguments),
    the numerics monitor's settings and the ambient distributed
    context. Raises :class:`CannotSay` where any of it is code from
    outside the package (a user's layer, vertex, updater or schedule
    class; a subclass of the net)."""
    from deeplearning4j_tpu.parallel.mesh import active_context

    ctx, nm = active_context(), getattr(net, "_numerics", None)
    return describe({
        "net": type(net),
        "conf": {k: v for k, v in vars(net.conf).items() if k != "seed"},
        "numerics": nm and {"every": nm.every,
                            "histograms": nm.histograms,
                            "raise": nm.raise_on_nonfinite},
        "context": ctx and {
            "axes": dict(ctx.mesh.shape),
            "devices": [d.id for d in ctx.mesh.devices.flat],
            "axis": ctx.axis_name, "batch": ctx.batch_axis,
            "head": ctx.head_axis}})

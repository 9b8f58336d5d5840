"""Device mesh construction + multi-host bring-up.

Reference mapping:
 - ``CudaAffinityManager`` device lists / ``ParallelWrapper`` worker
   placement → a ``jax.sharding.Mesh`` with named axes.
 - Spark/Aeron cluster formation (``SharedTrainingMaster``,
   ``MeshOrganizer``) → ``jax.distributed.initialize`` (coordination
   service) + one mesh spanning all hosts; ICI inside a slice, DCN
   across slices, chosen by XLA from device topology.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None
              ) -> Mesh:
    """Build a mesh with named axes, e.g. {"data": 4, "model": 2}.

    An axis size of -1 absorbs the remaining devices (like a reshape).
    """
    devices = list(devices if devices is not None else jax.devices())
    names = list(axes.keys())
    sizes = list(axes.values())
    n = len(devices)
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError(f"mesh needs {total} devices, have {n}")
    arr = np.array(devices[:total]).reshape(sizes)
    return Mesh(arr, axis_names=tuple(names))


def data_parallel_mesh(n: Optional[int] = None) -> Mesh:
    """All (or first n) devices on one 'data' axis — the ParallelWrapper
    topology."""
    return make_mesh({"data": n if n else -1})


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "data") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def enable_cpu_collectives() -> bool:
    """Multi-process collectives on the CPU backend need the gloo
    transport (the default XLA:CPU backend refuses cross-process
    computations outright). Must run before backends initialize; a
    process whose named platforms leave out the CPU is a no-op.
    Returns whether the option was applied."""
    named = str(jax.config.jax_platforms or "").lower()
    if named and "cpu" not in named:
        return False
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    return True


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> None:
    """Multi-host bring-up (reference: SharedTrainingMaster's Spark+Aeron
    bootstrap → jax coordination service). No-op when single-process.

    This is also the re-formation entry point for elastic fleets
    (``resilience/elastic.py``): a surviving host's fresh process
    image calls back in here with the NEW world size and the new
    generation's epoch-salted coordinator port.

    Example launcher (replaces spark-submit):
        DL4J_TPU_COORD=host0:1234 DL4J_TPU_NPROC=4 DL4J_TPU_PROC_ID=$i \
            python train.py
    """
    import os
    coordinator_address = coordinator_address or os.environ.get(
        "DL4J_TPU_COORD")
    if coordinator_address is None:
        return  # single process
    enable_cpu_collectives()
    if num_processes is None:
        num_processes = int(os.environ["DL4J_TPU_NPROC"])
    if process_id is None:          # NOT `or`: rank 0 is falsy
        process_id = int(os.environ["DL4J_TPU_PROC_ID"])
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)


def initialize_distributed_elastic(coordinator_address: str,
                                   num_processes: int,
                                   process_id: int,
                                   on_fault=None) -> bool:
    """Distributed bring-up for a PREEMPTIBLE fleet: same coordination
    service, but the runtime client is built with (a) a custom
    missed-heartbeat/fault callback instead of the stock one — the
    stock callback TERMINATES the process the moment the service
    reports any peer dead, which on a spot fleet is routine, not fatal
    (the elastic layer's bounded-timeout collectives surface the
    failure as an exception the re-formation path handles) — and (b)
    ``shutdown_on_destruction=False``, so a surviving process never
    blocks in (or aborts on) the exit-time shutdown barrier its dead
    peers can no longer join.

    Reaches into the runtime's distributed state (the public
    ``initialize`` does not expose either knob); any mismatch with
    this runtime's internals falls back to the stock bring-up and
    returns False — training still works there, but host loss then
    kills the whole fleet the old way."""
    import logging
    logger = logging.getLogger("deeplearning4j_tpu")
    enable_cpu_collectives()
    if num_processes <= 1:
        return True
    from jax._src import distributed as _dist
    state = _dist.global_state
    if getattr(state, "client", None) is not None:
        # caller bug, not a compat problem: distributed is already up
        # and a second bring-up can only corrupt it — surface loudly
        raise RuntimeError(
            "distributed runtime already initialized; elastic "
            "re-formation replaces the process image instead of "
            "re-initializing in place")
    try:
        from jaxlib import xla_extension as _xe
        port = coordinator_address.rsplit(":", 1)[1]
        cb = on_fault or (lambda status: logger.warning(
            "elastic: coordination fault (peer died?): %s", status))
        if process_id == 0 and state.service is None:
            state.service = _xe.get_distributed_runtime_service(
                "[::]:" + port, num_processes,
                heartbeat_interval=10, max_missing_heartbeats=10)
        state.client = _xe.get_distributed_runtime_client(
            coordinator_address, process_id, init_timeout=120,
            heartbeat_interval=10, max_missing_heartbeats=10,
            missed_heartbeat_callback=cb,
            shutdown_on_destruction=False, use_compression=True)
        state.client.connect()
        state.process_id = process_id
        state.num_processes = num_processes
        try:
            state.initialize_preemption_sync_manager()
        except Exception:           # pragma: no cover - best effort
            pass
        return True
    except Exception as e:          # internals moved: stock bring-up
        logger.warning(
            "elastic distributed bring-up unavailable on this runtime "
            "(%s); falling back to jax.distributed.initialize — host "
            "loss will NOT be survivable in-fleet", e)
        # undo any partial mutation or the stock initialize (which
        # refuses to run twice) fails too: rank 0's service may
        # already hold the coordinator port
        if getattr(state, "client", None) is not None:
            state.client = None
        if getattr(state, "service", None) is not None:
            try:
                state.service.shutdown()
            except Exception:       # pragma: no cover - best effort
                pass
            state.service = None
        initialize_distributed(coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
        return False


# ---------------------------------------------------------------------------
# ambient distributed context — lets high-level layers (nn.layers.*)
# pick up the active mesh without threading it through every apply()
# signature (the reference threads context via static singletons the
# same way, e.g. Nd4j.getAffinityManager). Thread-local so e.g.
# ParallelInference worker threads never see the training thread's
# mesh; the epoch counter lets jit caches detect that the ambient
# state they traced under has changed.
import threading as _threading

_TLS = _threading.local()
_CTX_EPOCH = [0]


def _stack() -> list:
    if not hasattr(_TLS, "stack"):
        _TLS.stack = []
    return _TLS.stack


class distributed_context:
    """Context manager installing a mesh as the ambient distributed
    context: layers with a ``sequence_parallel`` setting (e.g.
    MultiHeadAttention) route their attention over ``axis_name`` of
    this mesh while the context is active.

        with distributed_context(make_mesh({"seq": 8})):
            net.fit(...)      # attention runs sequence-parallel

    The context is per-thread. Networks whose layers consult it
    re-trace their jitted steps when the ambient state changes (see
    ``context_epoch``), so the same net object can fit inside and
    outside a context without stale traces.

    Composed parallelism: when the mesh carries MORE axes than the
    sequence axis (e.g. ``make_mesh({"data": 2, "seq": 2,
    "tensor": 2})`` — DP × SP × TP in ONE jitted step),
    ``batch_axis``/``head_axis`` name the axes the batch and
    attention-head dims are sharded over; sequence-parallel layers
    thread them into the ring's shard_map specs so the data/tensor
    shardings ride through the ring instead of being re-gathered at
    its boundary. DP gradient psums and TP matmul partials stay with
    GSPMD (param/batch NamedShardings on the jitted step) — the ring
    is the only manually-mapped region.
    """

    def __init__(self, mesh: Mesh, axis_name: str = "seq",
                 batch_axis: Optional[str] = None,
                 head_axis: Optional[str] = None):
        self.mesh = mesh
        self.axis_name = axis_name
        self.batch_axis = batch_axis
        self.head_axis = head_axis

    def __enter__(self):
        _stack().append(self)
        _CTX_EPOCH[0] += 1
        return self

    def __exit__(self, *exc):
        stack = _stack()
        if self in stack:          # tolerate out-of-order exits
            stack.remove(self)
        _CTX_EPOCH[0] += 1
        return False


def active_context() -> Optional["distributed_context"]:
    stack = _stack()
    return stack[-1] if stack else None


def context_epoch() -> int:
    """Monotone counter bumped on every context enter/exit — jit-cache
    invalidation key for nets with ambient-context-dependent layers."""
    return _CTX_EPOCH[0]

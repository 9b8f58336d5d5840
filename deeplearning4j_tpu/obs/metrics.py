"""Metrics registry — counters/gauges/histograms with Prometheus text
exposition served from a stdlib HTTP ``/metrics`` + ``/healthz``.

Reference: ``StatsListener``'s system/score metrics and
``PerformanceListener`` throughput lines (SURVEY §5) — but those are
per-listener, per-training-run views. This registry is *process-wide*:
the fit loops, data iterators, ``ParallelWrapper``,
``ParallelInference``, the retrace sentry, and the persistent compile
cache all publish into one namespace, scraped over HTTP in the
standard Prometheus text format (the serving-fleet story the north
star needs) and snapshotted into ``obs.report()`` for bench/dossier/
crash dumps.

Naming scheme (``dl4j_tpu_<subsystem>_<name>_<unit>``):

- ``dl4j_tpu_step_latency_seconds{entry=...}`` — per-entry-point step
  histogram (``MultiLayerNetwork.fit``, ``ComputationGraph.fit``, ...)
- ``dl4j_tpu_h2d_seconds_total`` / ``dl4j_tpu_device_sync_seconds_total``
  — where the step went (host→device feed vs blocking device sync)
- ``dl4j_tpu_fit_etl_seconds_total`` / ``dl4j_tpu_prefetch_*`` — ETL
- ``dl4j_tpu_worker_*{worker=...}`` — ParallelWrapper per-worker step
  latency, collective-sync wall time, heartbeat age / staleness
- ``dl4j_tpu_inference_*`` — ParallelInference queue depth, request
  latency, batch sizes
- ``dl4j_tpu_retrace_*`` / ``dl4j_tpu_compile_*`` — the perf
  subsystem's sentry and persistent-cache counters, re-exported as
  first-class families by a pull-time collector (no double counting:
  ``perf/`` stays the source of truth).

The server reuses the ``train/stats.py`` pattern: stdlib
``ThreadingHTTPServer``, ephemeral-port friendly, daemon thread.
"""
from __future__ import annotations

import json
import re
import threading
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from deeplearning4j_tpu.obs import trace as _trace

# latency buckets (seconds): sub-ms dispatch floors through multi-s
# compiles
LATENCY_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
SIZE_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

#: THE metric-family registry: every ``dl4j_tpu_*`` family name in the
#: package — registered families, pull-time collector families, and
#: the fleet aggregator's computed families — declared ONCE here.
#: ``tools/lint_instrumentation.py`` rule 6 keeps this table, the emit
#: sites, ``tools/tpu_watch.py``, and ``docs/OPS.md`` in lockstep so a
#: family can't drift into three spellings across producers and
#: consumers. Add the name here FIRST when introducing a family.
FAMILIES = {
    # fit/serve hot paths (this module)
    "dl4j_tpu_step_latency_seconds": "histogram",
    "dl4j_tpu_steps_total": "counter",
    "dl4j_tpu_h2d_seconds_total": "counter",
    "dl4j_tpu_device_sync_seconds_total": "counter",
    "dl4j_tpu_fit_etl_seconds_total": "counter",
    "dl4j_tpu_prefetch_wait_seconds_total": "counter",
    "dl4j_tpu_prefetch_depth": "gauge",
    "dl4j_tpu_worker_step_latency_seconds": "histogram",
    "dl4j_tpu_worker_collective_sync_seconds_total": "counter",
    "dl4j_tpu_worker_staged_ahead_total": "counter",
    "dl4j_tpu_worker_steps_ahead_total": "counter",
    "dl4j_tpu_inference_requests_total": "counter",
    "dl4j_tpu_inference_request_latency_seconds": "histogram",
    "dl4j_tpu_inference_queue_depth": "gauge",
    "dl4j_tpu_inference_batch_size": "histogram",
    # resilience + elastic membership
    "dl4j_tpu_resilience_restarts_total": "counter",
    "dl4j_tpu_inference_requests_shed_total": "counter",
    "dl4j_tpu_checkpoints_quarantined_total": "counter",
    "dl4j_tpu_faults_injected_total": "counter",
    "dl4j_tpu_preemptions_total": "counter",
    "dl4j_tpu_mesh_epoch": "gauge",
    "dl4j_tpu_hosts_evicted_total": "counter",
    # parallel training
    "dl4j_tpu_opt_state_bytes_per_device": "gauge",
    # perf collector (retrace sentry + persistent compile cache)
    "dl4j_tpu_retrace_traces_total": "counter",
    "dl4j_tpu_retrace_unplanned_shapes": "gauge",
    "dl4j_tpu_retrace_compiles_total": "counter",
    "dl4j_tpu_aot_hits_total": "counter",
    "dl4j_tpu_aot_store_hits_total": "counter",
    "dl4j_tpu_aot_store_misses_total": "counter",
    "dl4j_tpu_compile_time_seconds_total": "counter",
    "dl4j_tpu_compile_cache_requests_total": "counter",
    "dl4j_tpu_compile_cache_hits_total": "counter",
    # worker/host health collector
    "dl4j_tpu_worker_heartbeat_age_seconds": "gauge",
    "dl4j_tpu_worker_stale": "gauge",
    # numerics observatory (obs/numerics.py)
    "dl4j_tpu_numerics_grad_norm": "gauge",
    "dl4j_tpu_numerics_update_ratio": "gauge",
    "dl4j_tpu_numerics_activation_absmax": "gauge",
    "dl4j_tpu_numerics_replica_divergence": "gauge",
    "dl4j_tpu_numerics_param_replica_divergence": "gauge",
    "dl4j_tpu_numerics_nonfinite_total": "counter",
    "dl4j_tpu_numerics_diag_steps_total": "counter",
    # continuous-batching serving gateway (serving/)
    "dl4j_tpu_serving_requests_total": "counter",
    "dl4j_tpu_serving_requests_shed_total": "counter",
    "dl4j_tpu_serving_tokens_total": "counter",
    "dl4j_tpu_serving_ttft_seconds": "histogram",
    "dl4j_tpu_serving_step_seconds": "histogram",
    "dl4j_tpu_serving_steps_ahead_total": "counter",
    "dl4j_tpu_serving_prefill_seconds": "histogram",
    "dl4j_tpu_serving_active_slots": "gauge",
    "dl4j_tpu_serving_queue_depth": "gauge",
    "dl4j_tpu_serving_kv_pages_free": "gauge",
    "dl4j_tpu_serving_kv_page_occupancy": "gauge",
    "dl4j_tpu_serving_kv_pages_reserved": "gauge",
    "dl4j_tpu_serving_kv_pages_walked": "gauge",
    "dl4j_tpu_serving_kv_pages": "gauge",
    "dl4j_tpu_serving_kv_rows_read_total": "counter",
    "dl4j_tpu_serving_kv_rows_unwindowed_total": "counter",
    "dl4j_tpu_serving_ring_pages_overwritten_total": "counter",
    "dl4j_tpu_serving_state_pool_bytes": "gauge",
    "dl4j_tpu_serving_state_bytes_moved": "counter",
    "dl4j_tpu_serving_latent_rows_read_total": "counter",
    "dl4j_tpu_serving_expert_pairs_total": "counter",
    "dl4j_tpu_moe_expert_layers_traced_total": "counter",
    # speculative multi-token decode (serving/scheduler.py)
    "dl4j_tpu_serving_spec_accept_rate": "histogram",
    "dl4j_tpu_serving_spec_drafted_total": "counter",
    "dl4j_tpu_serving_spec_accepted_total": "counter",
    # copy-on-write prefix sharing (serving/kv_pager.py)
    "dl4j_tpu_serving_prefix_hits_total": "counter",
    "dl4j_tpu_serving_prefix_prefill_tokens_saved_total": "counter",
    "dl4j_tpu_serving_prefix_shared_pages": "gauge",
    "dl4j_tpu_serving_prefix_cow_copies_total": "counter",
    # device-time observatory (obs/devtime.py)
    "dl4j_tpu_devtime_captures_total": "counter",
    "dl4j_tpu_devtime_capture_seconds_total": "counter",
    "dl4j_tpu_devtime_scope_seconds": "gauge",
    "dl4j_tpu_devtime_scope_share": "gauge",
    "dl4j_tpu_devtime_scope_utilization": "gauge",
    "dl4j_tpu_devtime_scope_pallas_candidate": "gauge",
    "dl4j_tpu_devtime_pallas_candidates": "gauge",
    # communication observatory (obs/commtime.py)
    "dl4j_tpu_comm_captures_total": "counter",
    "dl4j_tpu_comm_capture_seconds_total": "counter",
    "dl4j_tpu_comm_scope_wire_bytes_per_step": "gauge",
    "dl4j_tpu_comm_scope_collective_seconds": "gauge",
    "dl4j_tpu_comm_scope_step_share": "gauge",
    "dl4j_tpu_comm_scope_link_utilization": "gauge",
    "dl4j_tpu_comm_op_count": "gauge",
    "dl4j_tpu_comm_wire_bound_scopes": "gauge",
    # fleet observability plane (obs/fleet.py)
    "dl4j_tpu_fleet_snapshots_published_total": "counter",
    "dl4j_tpu_flight_recorder_dumps_total": "counter",
    "dl4j_tpu_collective_skew_seconds": "gauge",
    "dl4j_tpu_collective_straggler": "gauge",
    "dl4j_tpu_fleet_hosts": "gauge",
    "dl4j_tpu_fleet_snapshot_age_seconds": "gauge",
    # elastic serving fleet: front-end router (serving/fleet.py)
    "dl4j_tpu_router_requests_total": "counter",
    "dl4j_tpu_router_sheds_total": "counter",
    "dl4j_tpu_router_reroutes_total": "counter",
    "dl4j_tpu_router_replicas_ready": "gauge",
    # elastic serving fleet: replica lifecycle (serving/fleet.py +
    # obs/fleet.py serving aggregation)
    "dl4j_tpu_serving_fleet_spawns_total": "counter",
    "dl4j_tpu_serving_fleet_evictions_total": "counter",
    "dl4j_tpu_serving_fleet_warm_buckets": "gauge",
    "dl4j_tpu_serving_fleet_replica_ready": "gauge",
}


def _escape(v: str) -> str:
    return str(v).replace("\\", r"\\").replace("\n", r"\n") \
        .replace('"', r'\"')


def _label_str(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class _Child:
    """One labelset's state. ``inc``/``set``/``observe`` are the hot
    path — a lock, a float add, and (histograms) one linear bucket
    scan over ~14 bounds."""

    __slots__ = ("_m", "value", "counts", "sum", "count", "fn")

    def __init__(self, metric: "Metric"):
        self._m = metric
        self.value = 0.0
        self.fn: Optional[Callable[[], float]] = None
        if metric.kind == "histogram":
            self.counts = [0] * len(metric.buckets)
            self.sum = 0.0
            self.count = 0

    def inc(self, amount: float = 1.0):
        with self._m._lock:
            self.value += amount

    def dec(self, amount: float = 1.0):
        self.inc(-amount)

    def set(self, value: float):
        with self._m._lock:
            self.value = float(value)

    def set_function(self, fn: Callable[[], float]):
        """Gauge evaluated at scrape time (queue depths, ages)."""
        self.fn = fn

    def observe(self, value: float):
        m = self._m
        with m._lock:
            self.sum += value
            self.count += 1
            for i, b in enumerate(m.buckets):
                if value <= b:
                    self.counts[i] += 1
                    break

    def get(self) -> float:
        if self.fn is not None:
            try:
                return float(self.fn())
            except Exception:
                return float("nan")
        return self.value


class Metric:
    """One metric family (counter | gauge | histogram), optionally
    labelled. ``labels(**kv)`` returns the cached per-labelset child;
    un-labelled families proxy the operations directly."""

    def __init__(self, kind: str, name: str, doc: str,
                 labelnames: Tuple[str, ...] = (),
                 buckets: Tuple[float, ...] = LATENCY_BUCKETS):
        self.kind = kind
        self.name = name
        self.doc = doc
        self.labelnames = tuple(labelnames)
        self.buckets = tuple(sorted(buckets))
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], _Child] = {}
        if not self.labelnames:
            self._children[()] = _Child(self)

    def labels(self, **kv: str) -> _Child:
        key = tuple(str(kv[n]) for n in self.labelnames)
        child = self._children.get(key)
        if child is None:
            with self._lock:
                child = self._children.setdefault(key, _Child(self))
        return child

    # un-labelled convenience
    def inc(self, amount: float = 1.0):
        self._children[()].inc(amount)

    def dec(self, amount: float = 1.0):
        self._children[()].dec(amount)

    def set(self, value: float):
        self._children[()].set(value)

    def set_function(self, fn: Callable[[], float]):
        self._children[()].set_function(fn)

    def observe(self, value: float):
        self._children[()].observe(value)

    # -- exposition ------------------------------------------------------
    def _samples(self) -> Iterable[Tuple[str, Dict[str, str], float]]:
        with self._lock:
            items = list(self._children.items())
        for key, child in items:
            labels = dict(zip(self.labelnames, key))
            if self.kind == "histogram":
                cum = 0
                for b, c in zip(self.buckets, child.counts):
                    cum += c
                    yield (self.name + "_bucket",
                           {**labels, "le": repr(float(b))}, cum)
                yield (self.name + "_bucket",
                       {**labels, "le": "+Inf"}, child.count)
                yield (self.name + "_sum", labels, child.sum)
                yield (self.name + "_count", labels, child.count)
            else:
                yield (self.name, labels, child.get())

    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        with self._lock:
            items = list(self._children.items())
        for key, child in items:
            lk = _label_str(dict(zip(self.labelnames, key))) or ""
            if self.kind == "histogram":
                out[lk] = {"count": child.count, "sum": child.sum}
            else:
                out[lk] = child.get()
        return out


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, Metric] = {}
        self._collectors: List[Callable[[], Iterable]] = []

    def _get_or_create(self, kind, name, doc, labelnames, buckets
                       ) -> Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = Metric(kind, name, doc, labelnames, buckets)
                self._metrics[name] = m
            elif m.kind != kind:
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}")
            return m

    def counter(self, name, doc, labelnames=()) -> Metric:
        return self._get_or_create("counter", name, doc, labelnames,
                                   LATENCY_BUCKETS)

    def gauge(self, name, doc, labelnames=()) -> Metric:
        return self._get_or_create("gauge", name, doc, labelnames,
                                   LATENCY_BUCKETS)

    def histogram(self, name, doc, labelnames=(),
                  buckets=LATENCY_BUCKETS) -> Metric:
        return self._get_or_create("histogram", name, doc, labelnames,
                                   buckets)

    def register_collector(self, fn: Callable[[], Iterable]) -> None:
        """``fn()`` → iterable of ``(name, kind, doc, samples)`` with
        ``samples = [(labels_dict, value), ...]``, evaluated at scrape
        time — how external counter sources (retrace sentry, compile
        cache, worker health) join the namespace without double
        bookkeeping."""
        with self._lock:
            self._collectors.append(fn)

    def _collected(self) -> List[Tuple[str, str, str, list]]:
        with self._lock:
            collectors = list(self._collectors)
        out = []
        for fn in collectors:
            try:
                out.extend(fn())
            except Exception:
                continue            # a broken collector never breaks /metrics
        return out

    def exposition(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        lines: List[str] = []
        with self._lock:
            metrics = list(self._metrics.values())
        for m in sorted(metrics, key=lambda m: m.name):
            lines.append(f"# HELP {m.name} {_escape(m.doc)}")
            lines.append(f"# TYPE {m.name} {m.kind}")
            for name, labels, value in m._samples():
                lines.append(f"{name}{_label_str(labels)} {value}")
        for name, kind, doc, samples in sorted(self._collected()):
            lines.append(f"# HELP {name} {_escape(doc)}")
            lines.append(f"# TYPE {name} {kind}")
            for labels, value in samples:
                lines.append(f"{name}{_label_str(labels)} {value}")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> Dict[str, Any]:
        """Plain-dict view of every family (registry metrics + collector
        families) — the ``metrics`` section of ``obs.report()``."""
        with self._lock:
            metrics = dict(self._metrics)
        out: Dict[str, Any] = {
            name: {"type": m.kind, "values": m.snapshot()}
            for name, m in metrics.items()}
        for name, kind, _doc, samples in self._collected():
            out[name] = {"type": kind, "values": {
                _label_str(labels) or "": value
                for labels, value in samples}}
        return out

    def reset(self) -> None:
        """Tests only: zero every family IN PLACE (collectors kept).
        The family objects stay registered — module-level handles like
        ``STEP_SECONDS`` keep working — only their labelsets/values are
        dropped; clearing ``_metrics`` instead would orphan every
        standing handle and silently swallow later instrumentation."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            with m._lock:
                m._children.clear()
                if not m.labelnames:
                    m._children[()] = _Child(m)


REGISTRY = MetricsRegistry()


def snapshot() -> Dict[str, Any]:
    return REGISTRY.snapshot()


def exposition() -> str:
    return REGISTRY.exposition()

# -- the package's standing instrumentation families -------------------------

STEP_SECONDS = REGISTRY.histogram(
    "dl4j_tpu_step_latency_seconds",
    "end-to-end train/serve step latency (h2d + dispatch + sync)",
    ("entry",))
STEPS = REGISTRY.counter(
    "dl4j_tpu_steps_total", "completed steps per entry point", ("entry",))
H2D_SECONDS = REGISTRY.counter(
    "dl4j_tpu_h2d_seconds_total",
    "host->device feed time (array conversion/stacking)", ("entry",))
SYNC_SECONDS = REGISTRY.counter(
    "dl4j_tpu_device_sync_seconds_total",
    "blocking device sync time (loss/result to host)", ("entry",))
FIT_ETL_SECONDS = REGISTRY.counter(
    "dl4j_tpu_fit_etl_seconds_total",
    "time the fit loop waited on its data iterator", ("entry",))
PREFETCH_WAIT = REGISTRY.counter(
    "dl4j_tpu_prefetch_wait_seconds_total",
    "consumer wait on the AsyncDataSetIterator queue")
PREFETCH_DEPTH = REGISTRY.gauge(
    "dl4j_tpu_prefetch_depth",
    "AsyncDataSetIterator queue depth after the last get")
WORKER_STEP = REGISTRY.histogram(
    "dl4j_tpu_worker_step_latency_seconds",
    "ParallelWrapper per-worker step latency", ("worker",))
WORKER_SYNC = REGISTRY.counter(
    "dl4j_tpu_worker_collective_sync_seconds_total",
    "ParallelWrapper wait for step + averaging/all-reduce completion",
    ("worker",))
WORKER_STAGED_AHEAD = REGISTRY.counter(
    "dl4j_tpu_worker_staged_ahead_total",
    "ParallelWrapper steps dispatched on a batch staged onto the mesh "
    "during the step before (over the step-latency count: the share "
    "of steps whose host-to-device copy had compute to hide behind)",
    ("worker",))
WORKER_AHEAD = REGISTRY.counter(
    "dl4j_tpu_worker_steps_ahead_total",
    "ParallelWrapper steps launched before their predecessor's loss "
    "was read (over the step-latency count: the share of steps whose "
    "launch and read-back the chips did not wait for)",
    ("worker",))
INFER_REQS = REGISTRY.counter(
    "dl4j_tpu_inference_requests_total",
    "ParallelInference requests enqueued")
INFER_LATENCY = REGISTRY.histogram(
    "dl4j_tpu_inference_request_latency_seconds",
    "enqueue->result latency per request")
INFER_QUEUE = REGISTRY.gauge(
    "dl4j_tpu_inference_queue_depth",
    "ParallelInference request queue depth")
INFER_BATCH = REGISTRY.histogram(
    "dl4j_tpu_inference_batch_size",
    "examples per dispatched serving batch", buckets=SIZE_BUCKETS)

# resilience subsystem (resilience/ + train/fault_tolerance.py)
RESILIENCE_RESTARTS = REGISTRY.counter(
    "dl4j_tpu_resilience_restarts_total",
    "restore-and-continue restarts by FaultTolerantTrainer")
REQS_SHED = REGISTRY.counter(
    "dl4j_tpu_inference_requests_shed_total",
    "serving requests shed instead of served", ("reason",))
CKPT_QUARANTINED = REGISTRY.counter(
    "dl4j_tpu_checkpoints_quarantined_total",
    "corrupt/partial checkpoints moved to corrupt/")
FAULTS_INJECTED = REGISTRY.counter(
    "dl4j_tpu_faults_injected_total",
    "faults fired by the DL4J_TPU_FAULT_PLAN harness", ("site",))
PREEMPTIONS = REGISTRY.counter(
    "dl4j_tpu_preemptions_total",
    "SIGTERM preemption notices honored (checkpoint-and-exit)")

# elastic multi-host training (resilience/elastic.py): the committed
# membership generation every step is stamped with, and the hosts the
# coordinator has evicted (missed lease / SIGTERM departure)
MESH_EPOCH = REGISTRY.gauge(
    "dl4j_tpu_mesh_epoch",
    "committed mesh-membership generation this host trains under")
HOSTS_EVICTED = REGISTRY.counter(
    "dl4j_tpu_hosts_evicted_total",
    "hosts forcibly evicted from the fleet after a missed lease "
    "(graceful SIGTERM departures count preemptions_total instead)")

# continuous-batching serving gateway (serving/): in-flight batched
# decode over the paged KV cache — TTFT is the serving SLO metric
# (queue wait + prefill), step_seconds is the wall time a decode step
# adds (the gap between two tokens of every active slot while the
# loop keeps a step in flight), kv_pages_free is the
# admission-control currency
SERVING_REQS = REGISTRY.counter(
    "dl4j_tpu_serving_requests_total",
    "gateway requests submitted (per tenant)", ("tenant",))
SERVING_SHED = REGISTRY.counter(
    "dl4j_tpu_serving_requests_shed_total",
    "gateway requests shed instead of served", ("reason",))
SERVING_TOKENS = REGISTRY.counter(
    "dl4j_tpu_serving_tokens_total",
    "tokens streamed by the continuous-batching gateway")
SERVING_TTFT = REGISTRY.histogram(
    "dl4j_tpu_serving_ttft_seconds",
    "submit -> first streamed token (queue wait + paged prefill)")
SERVING_STEP = REGISTRY.histogram(
    "dl4j_tpu_serving_step_seconds",
    "wall time one fixed-shape continuous-batching decode step adds, "
    "observed once a device step when its tokens are read: from the "
    "read before it (with a step in flight: the gap between two "
    "tokens of every active slot), or from its own launch where that "
    "came later (the first step after a drain: launch, device time "
    "and read-back)")
SERVING_AHEAD = REGISTRY.counter(
    "dl4j_tpu_serving_steps_ahead_total",
    "decode steps launched before their predecessor's tokens were "
    "read (over the step-seconds count: the share of steps whose "
    "launch and read-back the device did not wait for)")
SERVING_PREFILL = REGISTRY.histogram(
    "dl4j_tpu_serving_prefill_seconds",
    "prompt prefill-into-pages wall time per admission")
SERVING_SLOTS = REGISTRY.gauge(
    "dl4j_tpu_serving_active_slots",
    "decode slots occupied by in-flight sequences")
SERVING_QUEUE = REGISTRY.gauge(
    "dl4j_tpu_serving_queue_depth",
    "requests queued awaiting admission (all tenants)")
SERVING_PAGES_FREE = REGISTRY.gauge(
    "dl4j_tpu_serving_kv_pages_free",
    "free pages in the paged KV-cache pool")
SERVING_KV_OCCUPANCY = REGISTRY.gauge(
    "dl4j_tpu_serving_kv_page_occupancy",
    "fraction of usable KV pages currently reserved by live "
    "sequences (1.0 = admission-control full)")
SERVING_KV_RESERVED = REGISTRY.gauge(
    "dl4j_tpu_serving_kv_pages_reserved",
    "KV pages reserved per tenant (whole-life reservations, the "
    "admission-control currency)", ("tenant",))
SERVING_KV_WALKED = REGISTRY.gauge(
    "dl4j_tpu_serving_kv_pages_walked",
    "KV pages the last decode step's attention read: the sum over "
    "active slots of ceil(length / block), against max_slots x "
    "max_pages_per_seq page-table entries")
SERVING_KV_PAGES_HELD = REGISTRY.gauge(
    "dl4j_tpu_serving_kv_pages",
    "KV pages live sequences hold, by kind, of a pool with two kinds "
    "of KV pages (a windowed decoder's): 'full' pages off the free "
    "list, 'window' pages of the slots' rings (at most ring a "
    "sequence)", ("kind",))
SERVING_KV_ROWS_READ = REGISTRY.counter(
    "dl4j_tpu_serving_kv_rows_read_total",
    "cached positions the decode steps' page walks of a windowed "
    "decoder had to read, over all its layers: a full layer every "
    "live position, a window layer the last window of them")
SERVING_KV_ROWS_UNWINDOWED = REGISTRY.counter(
    "dl4j_tpu_serving_kv_rows_unwindowed_total",
    "cached positions the same steps would have read had every layer "
    "kept every position (1 - read / this: the share the window saves)")
SERVING_RING_OVERWRITES = REGISTRY.counter(
    "dl4j_tpu_serving_ring_pages_overwritten_total",
    "ring pages of window layers that a decode step began to write "
    "over (their positions had left every later query's window)")
SERVING_STATE_POOL = REGISTRY.gauge(
    "dl4j_tpu_serving_state_pool_bytes",
    "bytes of the recurrent-state pool as stored (0 for a KV-page "
    "pool): one fixed-size page a sequence, trash page included; a "
    "hybrid decoder's states and convolution tails, beside its KV pages")
SERVING_STATE_MOVED = REGISTRY.counter(
    "dl4j_tpu_serving_state_bytes_moved",
    "bytes of recurrent state the decode steps have read and written "
    "(a retention model's logical size d(d+1)/2 rows a kv head, float32; "
    "a hybrid's Mamba states and tails; both directions)")
SERVING_LATENT_ROWS = REGISTRY.counter(
    "dl4j_tpu_serving_latent_rows_read_total",
    "cached positions the decode steps' latent attention has read: "
    "each step the sum of its live slots' lengths, one latent row a "
    "position and layer (0 for a model without a latent pool)")
SERVING_EXPERT_PAIRS = REGISTRY.counter(
    "dl4j_tpu_serving_expert_pairs_total",
    "token-expert pairs the experts held here have computed, over "
    "all expert layers, decode steps and prefills (pairs routed to "
    "experts that other chips hold are not counted)")
MOE_EXPERT_LAYERS = REGISTRY.counter(
    "dl4j_tpu_moe_expert_layers_traced_total",
    "expert layers TRACED by the form ops.moe.experts chose for them "
    "from their shapes (path=kernel: the pipelined tile kernel; "
    "path=loop: the tile loop): decided once a program, so a program "
    "loaded by its key counts nothing", ("path",))

# speculative multi-token decode + copy-on-write prefix sharing
# (serving/scheduler.py + serving/kv_pager.py): accept rate is the
# fraction of drafted tokens the verify step confirmed (1.0 = every
# draft landed — the k-for-one win), prefix counters record admissions
# that rode an existing page chain and the prefill tokens that saved
SERVING_SPEC_ACCEPT = REGISTRY.histogram(
    "dl4j_tpu_serving_spec_accept_rate",
    "per-slot fraction of drafted tokens accepted by one verify step",
    buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
SERVING_SPEC_DRAFTED = REGISTRY.counter(
    "dl4j_tpu_serving_spec_drafted_total",
    "tokens drafted by the host-side prompt-lookup draft")
SERVING_SPEC_ACCEPTED = REGISTRY.counter(
    "dl4j_tpu_serving_spec_accepted_total",
    "drafted tokens accepted by the batched verify step")
SERVING_PREFIX_HITS = REGISTRY.counter(
    "dl4j_tpu_serving_prefix_hits_total",
    "admissions that mapped a shared prompt prefix onto an existing "
    "page chain (prefill ran only on the novel suffix)")
SERVING_PREFIX_SAVED = REGISTRY.counter(
    "dl4j_tpu_serving_prefix_prefill_tokens_saved_total",
    "prompt tokens NOT prefilled because their pages were shared")
SERVING_PREFIX_SHARED = REGISTRY.gauge(
    "dl4j_tpu_serving_prefix_shared_pages",
    "KV pages currently referenced by more than one live sequence")
SERVING_PREFIX_COW = REGISTRY.counter(
    "dl4j_tpu_serving_prefix_cow_copies_total",
    "copy-on-write page copies (a write hit a shared page)")

# elastic serving fleet (serving/fleet.py): the front-end router's
# admission/shed/re-route ledger plus the replica-lifecycle counters
# the autoscale drill asserts against (ARCHITECTURE.md §20)
ROUTER_REQS = REGISTRY.counter(
    "dl4j_tpu_router_requests_total",
    "requests the front-end router forwarded, by replica",
    ("replica",))
ROUTER_SHEDS = REGISTRY.counter(
    "dl4j_tpu_router_sheds_total",
    "in-flight streams structurally shed by the router (every one "
    "surfaced as SequenceAborted — never a hung client)", ("reason",))
ROUTER_REROUTES = REGISTRY.counter(
    "dl4j_tpu_router_reroutes_total",
    "requests re-submitted to a different replica after their first "
    "replica died or refused")
ROUTER_READY = REGISTRY.gauge(
    "dl4j_tpu_router_replicas_ready",
    "replicas the router currently considers routable (lease live "
    "AND warmup-ready)")
FLEET_SPAWNS = REGISTRY.counter(
    "dl4j_tpu_serving_fleet_spawns_total",
    "replicas spawned by the fleet supervisor to restore target "
    "capacity after an eviction")
FLEET_EVICTIONS = REGISTRY.counter(
    "dl4j_tpu_serving_fleet_evictions_total",
    "serving replicas evicted from the membership plane (lease "
    "expired)")
FLEET_WARM_BUCKETS = REGISTRY.gauge(
    "dl4j_tpu_serving_fleet_warm_buckets",
    "warmup buckets this replica has AOT-compiled (readiness = all "
    "declared buckets warm)")

# device-time observatory (obs/devtime.py): short profiler windows
# attributed to the named_scope'd layers — the instrument that names
# the Pallas gaps (ARCHITECTURE.md §16)
DEVTIME_CAPTURES = REGISTRY.counter(
    "dl4j_tpu_devtime_captures_total",
    "completed device-time capture-and-attribute pipelines")
DEVTIME_CAPTURE_SECONDS = REGISTRY.counter(
    "dl4j_tpu_devtime_capture_seconds_total",
    "wall time spent inside capture windows (profiler session + "
    "xplane parse + attribution) — the capture-cost budget meter")
DEVTIME_SCOPE_SECONDS = REGISTRY.gauge(
    "dl4j_tpu_devtime_scope_seconds",
    "device seconds per scope over the LAST capture window",
    ("scope",))
DEVTIME_SCOPE_SHARE = REGISTRY.gauge(
    "dl4j_tpu_devtime_scope_share",
    "share of measured device time per scope (last capture)",
    ("scope",))
DEVTIME_SCOPE_UTILIZATION = REGISTRY.gauge(
    "dl4j_tpu_devtime_scope_utilization",
    "achieved-vs-roofline utilization of the binding resource per "
    "scope (last capture; DL4J_TPU_PEAK_TFLOPS/_PEAK_HBM_GBS peaks)",
    ("scope",))
DEVTIME_SCOPE_CANDIDATE = REGISTRY.gauge(
    "dl4j_tpu_devtime_scope_pallas_candidate",
    "1 when the last gap report flagged this scope as a Pallas "
    "candidate (the AUTHORITATIVE flag — consumers must read it, "
    "not re-derive the rule)", ("scope",))
DEVTIME_PALLAS_CANDIDATES = REGISTRY.gauge(
    "dl4j_tpu_devtime_pallas_candidates",
    "scopes the last gap report flagged as Pallas-kernel candidates "
    "(high share, low utilization, not already a custom call)")

# communication observatory (obs/commtime.py): the wire sibling of the
# devtime plane — per-scope collective time, static HLO wire bytes,
# and interconnect-roofline utilization (ARCHITECTURE.md §19)
COMM_CAPTURES = REGISTRY.counter(
    "dl4j_tpu_comm_captures_total",
    "completed communication capture-and-attribute pipelines")
COMM_CAPTURE_SECONDS = REGISTRY.counter(
    "dl4j_tpu_comm_capture_seconds_total",
    "wall time spent inside comm capture windows (profiler session + "
    "xplane parse + ledger join)")
COMM_SCOPE_WIRE_BYTES = REGISTRY.gauge(
    "dl4j_tpu_comm_scope_wire_bytes_per_step",
    "ring-model wire bytes per step per scope from the static HLO "
    "ledger of the captured executables (last capture)", ("scope",))
COMM_SCOPE_SECONDS = REGISTRY.gauge(
    "dl4j_tpu_comm_scope_collective_seconds",
    "device seconds spent inside collective ops per scope over the "
    "LAST capture window", ("scope",))
COMM_SCOPE_SHARE = REGISTRY.gauge(
    "dl4j_tpu_comm_scope_step_share",
    "share of total measured device time this scope spent in "
    "collectives (last capture) — the WIRE_BOUND alarm input",
    ("scope",))
COMM_SCOPE_LINK_UTILIZATION = REGISTRY.gauge(
    "dl4j_tpu_comm_scope_link_utilization",
    "achieved interconnect GB/s over DL4J_TPU_PEAK_ICI_GBS per scope "
    "(last capture; estimate-only off TPU)", ("scope",))
COMM_OP_COUNT = REGISTRY.gauge(
    "dl4j_tpu_comm_op_count",
    "collective op executions per kind over the last capture window",
    ("kind",))
COMM_WIRE_BOUND_SCOPES = REGISTRY.gauge(
    "dl4j_tpu_comm_wire_bound_scopes",
    "scopes the last comm capture flagged wire-bound (collective time "
    "dominates the scope's device time) — 1 per flagged scope, the "
    "AUTHORITATIVE flag set tpu_watch --comm renders", ("scope",))

# parallel training (parallel/wrapper.py): the optimizer-state HBM
# footprint the ZeRO sharded update divides by N — layout is
# "replicated" (every device holds full moments) or "sharded" (1/N)
OPT_STATE_BYTES = REGISTRY.gauge(
    "dl4j_tpu_opt_state_bytes_per_device",
    "optimizer-state bytes resident per device for the active "
    "ParallelWrapper training layout", ("layout",))


def drop_entry(entry: str) -> None:
    """Remove one ``entry`` labelset from every per-entry family —
    used by ``obs.overhead_report`` to scrub its probe iterations so
    synthetic samples never reach /metrics or step summaries."""
    for fam in (STEP_SECONDS, STEPS, H2D_SECONDS, SYNC_SECONDS,
                FIT_ETL_SECONDS):
        with fam._lock:
            fam._children.pop((entry,), None)


def observe_step(entry: str, dt: float, h2d: float = 0.0,
                 sync: float = 0.0) -> None:
    """One call per completed step — the always-on metrics half of
    ``obs.record_step`` (a handful of dict lookups and float adds)."""
    STEP_SECONDS.labels(entry=entry).observe(dt)
    STEPS.labels(entry=entry).inc()
    if h2d:
        H2D_SECONDS.labels(entry=entry).inc(h2d)
    if sync:
        SYNC_SECONDS.labels(entry=entry).inc(sync)


def step_summary() -> Dict[str, Dict[str, float]]:
    """Per-entry {count, mean_ms} — the compact step view embedded in
    StatsListener records."""
    out = {}
    for lk, s in STEP_SECONDS.snapshot().items():
        if not s["count"]:
            continue
        entry = lk[len('{entry="'):-2] if lk.startswith('{entry="') \
            else lk
        out[entry] = {"count": s["count"],
                      "mean_ms": s["sum"] / s["count"] * 1e3}
    return out


# -- pull-time collectors: perf subsystem + worker health --------------------

def _perf_collector():
    """Re-export the retrace sentry and persistent compile cache as
    metric families (read at scrape; ``perf/`` owns the counters)."""
    from deeplearning4j_tpu.perf import compile_cache, sentry
    st = sentry.stats()
    rows = list(st.items())
    yield ("dl4j_tpu_retrace_traces_total", "counter",
           "distinct tracings per sentried jit entry point",
           [({"function": n}, s["traces"]) for n, s in rows])
    yield ("dl4j_tpu_retrace_unplanned_shapes", "gauge",
           "distinct UNPLANNED traced shapes (the retrace budget meter)",
           [({"function": n}, s["unplanned_shapes"]) for n, s in rows])
    yield ("dl4j_tpu_retrace_compiles_total", "counter",
           "compiles observed on live calls per entry point",
           [({"function": n}, s["compiles"]) for n, s in rows])
    yield ("dl4j_tpu_aot_hits_total", "counter",
           "live calls served by a warmed AOT executable",
           [({"function": n}, s["aot_hits"]) for n, s in rows])
    yield ("dl4j_tpu_aot_store_hits_total", "counter",
           "executables loaded from the compile store by a key that "
           "needed no trace",
           [({"function": n}, s["store_hits"]) for n, s in rows])
    yield ("dl4j_tpu_aot_store_misses_total", "counter",
           "keyed programs the compile store did not hold: traced, "
           "compiled and put",
           [({"function": n}, s["store_misses"]) for n, s in rows])
    yield ("dl4j_tpu_compile_time_seconds_total", "counter",
           "wall time XLA spent compiling sentried entry points",
           [({}, sentry.total_compile_time_s())])
    c = compile_cache.counters()
    yield ("dl4j_tpu_compile_cache_requests_total", "counter",
           "compile requests eligible for the persistent XLA cache",
           [({}, c["compile_requests"])])
    yield ("dl4j_tpu_compile_cache_hits_total", "counter",
           "persistent XLA cache hits", [({}, c["persistent_hits"])])


def _health_collector():
    from deeplearning4j_tpu.obs import health
    chk = health.check()
    yield ("dl4j_tpu_worker_heartbeat_age_seconds", "gauge",
           "seconds since each worker's last heartbeat",
           [({"worker": w}, round(s["age_s"], 3))
            for w, s in chk.items()])
    yield ("dl4j_tpu_worker_stale", "gauge",
           "1 when a worker's heartbeat is older than "
           "DL4J_TPU_STALE_WORKER_SECS",
           [({"worker": w}, int(s["stale"])) for w, s in chk.items()])


REGISTRY.register_collector(_perf_collector)
REGISTRY.register_collector(_health_collector)


# -- scrape-side parser (tpu_watch + tests) ----------------------------------

_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+([^\s]+)$')
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_exposition(text: str) -> Dict[Tuple[str, Tuple], float]:
    """Parse Prometheus text exposition into
    ``{(name, ((label, value), ...)): float}`` — used by
    ``tools/tpu_watch.py`` when scraping a live run and by the tests
    that assert the exposition is well-formed."""
    out: Dict[Tuple[str, Tuple], float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"unparseable exposition line: {line!r}")
        name, labelblob, value = m.groups()
        labels = tuple(sorted(
            (k, v.replace(r'\"', '"').replace(r"\n", "\n")
             .replace(r"\\", "\\"))
            for k, v in _LABEL_RE.findall(labelblob or "")))
        out[(name, labels)] = float(value)
    return out


# -- /metrics + /healthz server ----------------------------------------------

#: readiness probes consulted by ``/healthz``: name -> zero-arg
#: callable returning truthy when ready. Readiness ≠ liveness — a
#: replica that is alive but still AOT-compiling its warmup buckets
#: answers 503 with status "warming", so a router never routes a
#: request that would cold-trace (serving/fleet.py registers one per
#: gateway; empty registry = always ready, the pre-fleet behavior)
_readiness: Dict[str, Any] = {}


def register_readiness(name: str, probe) -> None:
    """Add/replace a named readiness probe (None removes it)."""
    if probe is None:
        _readiness.pop(name, None)
    else:
        _readiness[name] = probe


def readiness() -> Dict[str, bool]:
    """Evaluate every registered probe (a raising probe reads as not
    ready — never as a dropped healthz)."""
    out = {}
    for name, probe in sorted(_readiness.items()):
        try:
            out[name] = bool(probe())
        except Exception:
            out[name] = False
    return out


#: shared elastic dir the ``/fleet`` path aggregates over (None = 404)
_fleet_dir: Optional[str] = None


def set_fleet_dir(directory) -> None:
    """Point the standing server's ``/fleet`` path at a fleet plane's
    shared directory: the endpoint then serves the MERGED fleet
    exposition (every host's families with ``host=``/``mesh_epoch=``
    labels plus collective-skew attribution) next to the per-process
    ``/metrics`` — one server, both altitudes."""
    global _fleet_dir
    _fleet_dir = None if directory is None else str(directory)


class MetricsServer:
    """Stdlib HTTP endpoint: ``/metrics`` (Prometheus text),
    ``/healthz`` (JSON liveness: 200 when no worker is stale, 503
    otherwise), ``/fleet`` (merged fleet exposition when
    :func:`set_fleet_dir` configured one). Pattern shared with
    ``train.stats.UIServer``."""

    def __init__(self, port: int = 0, registry: MetricsRegistry = None):
        self.port = port
        self.registry = registry or REGISTRY
        self._httpd = None
        self._thread = None
        self._t_start = _trace.now()

    def healthz(self) -> Dict[str, Any]:
        from deeplearning4j_tpu.obs import health
        chk = health.check()
        stale = sorted(w for w, s in chk.items() if s["stale"])
        ready = readiness()
        warming = sorted(n for n, ok in ready.items() if not ok)
        status = "ok"
        if warming:
            status = "warming"
        if stale:
            status = "stale_workers"
        return {
            "status": status,
            # readiness gate (serving fleet): probes registered via
            # register_readiness — 503/"warming" until every one is
            # true (a cold replica must not take traffic)
            "ready": not warming,
            "warming": warming,
            # ONE staleness table: worker heartbeats and elastic host
            # leases (mirrored in via health.observe_age with their
            # own lease window) — stale_hosts is the host: subset with
            # the prefix stripped, so a 503 names dying PEERS next to
            # wedged local workers with no divergent verdicts
            "stale_workers": stale,
            "stale_hosts": [w[len("host:"):] for w in stale
                            if w.startswith("host:")],
            "workers": {w: round(s["age_s"], 3)
                        for w, s in chk.items()},
            "uptime_s": round(_trace.now() - self._t_start, 3),
            "tracing": _trace.enabled(),
        }

    def start(self) -> "MetricsServer":
        import http.server

        srv = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    body = srv.registry.exposition().encode()
                    code, ctype = 200, \
                        "text/plain; version=0.0.4; charset=utf-8"
                elif path == "/healthz":
                    h = srv.healthz()
                    body = json.dumps(h).encode()
                    code = 200 if h["status"] == "ok" else 503
                    ctype = "application/json"
                elif path == "/fleet":
                    if _fleet_dir is None:
                        body = b"no fleet dir configured "\
                               b"(obs.metrics.set_fleet_dir)\n"
                        code, ctype = 404, "text/plain"
                    else:
                        try:
                            from deeplearning4j_tpu.obs import fleet
                            body = fleet.aggregate(_fleet_dir)\
                                .exposition().encode()
                            code, ctype = 200, \
                                "text/plain; version=0.0.4; " \
                                "charset=utf-8"
                        except Exception as e:
                            # a shared-FS hiccup must answer 500, not
                            # drop the socket mid-request
                            body = f"fleet aggregation failed: " \
                                   f"{e!r}\n".encode()
                            code, ctype = 500, "text/plain"
                else:
                    body = (b"deeplearning4j_tpu telemetry: "
                            b"/metrics /healthz /fleet\n")
                    code, ctype = 200, "text/plain"
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        self._httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None


_server: Optional[MetricsServer] = None


def start_server(port: Optional[int] = None) -> MetricsServer:
    """Start (or return) the process-wide telemetry endpoint. ``port``
    defaults to ``DL4J_TPU_METRICS_PORT`` (0 → ephemeral)."""
    global _server
    if _server is not None:
        return _server
    if port is None:
        from deeplearning4j_tpu import environment
        port = environment.get_flag("DL4J_TPU_METRICS_PORT")
    _server = MetricsServer(port=int(port)).start()
    return _server


def stop_server() -> None:
    global _server
    if _server is not None:
        _server.stop()
        _server = None

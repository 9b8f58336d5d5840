"""Synthetic serving-trace driver — shell CLI over
``deeplearning4j_tpu/serving/loadgen.py``.

Drives sustained multi-tenant load (open- or closed-loop) against a
live continuous-batching gateway and prints the serving SLO quartet:
p50/p99 TTFT, per-token latency, tokens/sec, shed rate — plus the
request-at-a-time ``generate()`` baseline for the speedup column. The
same numbers flow through the ``dl4j_tpu_serving_*`` metric families,
so a run with ``DL4J_TPU_METRICS_PORT`` set is scrapeable (and
``tools/tpu_watch.py`` renders a ``serving`` view per sample).

    python tools/serving_trace.py --smoke                 # small wiring run
    python tools/serving_trace.py --shared-prefix         # CoW + spec preset
    python tools/serving_trace.py --mode open --rate 200 \\
        --requests 256 --tenants 4 --slots 16             # open-loop sweep
    python tools/serving_trace.py --mode closed --clients 32 --baseline
    python tools/serving_trace.py --mode burst --prefix-sharing \\
        --spec-k 4                                        # custom shared run

The ``--shared-prefix`` preset runs ``loadgen.shared_prefix_report``:
one long system prompt shared across tenants, baseline gateway vs the
prefix-sharing + speculative-decode gateway, reporting prefix-hit
rate and prefill tokens saved beside the TTFT/tokens-sec speedups.

Exit status 0; one JSON report on stdout (last line).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> int:
    from deeplearning4j_tpu.serving import ServingGateway, loadgen
    from deeplearning4j_tpu.zoo import CausalTransformerLM, GPTMini

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="the small wiring configuration "
                         "(loadgen.smoke_report) and exit")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="the spec-decode + prefix-sharing acceptance "
                         "row (loadgen.shared_prefix_report) and exit")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="speculative decode width (1 = single-token)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="enable copy-on-write prefix sharing")
    ap.add_argument("--mode", choices=("open", "closed", "burst"),
                    default="closed")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="open-loop arrival rate (req/s)")
    ap.add_argument("--clients", type=int, default=16,
                    help="closed-loop concurrent callers")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--tenants", type=int, default=2)
    ap.add_argument("--prompt-lens", default="4:48",
                    help="lo:hi prompt length bounds")
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="pool pages (0 = full capacity)")
    ap.add_argument("--max-context", type=int, default=0)
    ap.add_argument("--queue-limit", type=int, default=128)
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request admission deadline (s)")
    ap.add_argument("--model", choices=("smoke", "mini"),
                    default="smoke")
    ap.add_argument("--baseline", action="store_true",
                    help="also measure request-at-a-time generate()")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.smoke:
        print(json.dumps(loadgen.smoke_report()))
        return 0
    if args.shared_prefix:
        print(json.dumps(loadgen.shared_prefix_report()))
        return 0

    if args.model == "mini":
        model = GPTMini(compute_dtype=None)
    else:
        model = CausalTransformerLM(vocab_size=512, hidden=256,
                                    n_layers=4, n_heads=4,
                                    n_kv_heads=2, max_len=256, seed=3)
    net = model.init()
    lo, hi = (int(x) for x in args.prompt_lens.split(":"))
    mc = args.max_context or min(
        model.max_len,
        ((hi + args.max_new + args.block - 1) // args.block + 1)
        * args.block)
    requests = loadgen.gen_requests(
        n_requests=args.requests,
        tenants=tuple(f"tenant{i}" for i in range(args.tenants)),
        prompt_lens=(lo, hi), max_new=args.max_new,
        vocab_size=model.vocab_size, seed=args.seed)

    report = {"model": args.model, "slots": args.slots,
              "block": args.block, "max_context": mc,
              "spec_k": args.spec_k,
              "prefix_sharing": args.prefix_sharing}
    if args.baseline:
        # full warm pass first: every prompt BUCKET must compile
        # before the timed run, or cold jits deflate the baseline and
        # overstate the speedup column
        loadgen.baseline_tokens_per_sec(model, net, requests)
        report["request_at_a_time_tokens_per_sec"] = round(
            loadgen.baseline_tokens_per_sec(model, net, requests), 2)

    gw = ServingGateway(model, net, max_slots=args.slots,
                        block=args.block,
                        n_pages=args.pages or None, max_context=mc,
                        queue_limit=args.queue_limit,
                        default_max_new=args.max_new,
                        spec_k=args.spec_k,
                        prefix_sharing=args.prefix_sharing)
    report["warmup"] = gw.warmup(prompt_lens=range(1, hi + 1))
    stats = loadgen.run_trace(gw, requests, mode=args.mode,
                              rate=args.rate, clients=args.clients,
                              deadline_s=args.deadline)
    gw.shutdown()
    report.update(stats)
    if args.baseline and stats["tokens_per_sec"]:
        report["speedup"] = round(
            stats["tokens_per_sec"]
            / report["request_at_a_time_tokens_per_sec"], 3)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Find the knee of a serving cell once, on the chip: the highest rate
the system sustains. ONE process and one set-up; a window at each rate
in turn, every request followed to its end before the next rate.

    python3 benchmarks/tools/find_knee.py --workload <cell> \\
        --rates 4,6,8,10,12,14,16 --seconds 20 --seeds 1

With several ``--seeds`` every rate gets a window a seed (the weights
are the first seed's): how far a cell's tails differ from seed to seed
at another window length, without a set-up a window. ``--head S`` adds
the tails over the requests due in a window's first ``S`` seconds.

A rate is sustained when its queue does not grow across its window:
the requests waiting, and the wait of those admitted, are no larger in
the window's second half than in its first. The cells' ``rate_per_s``
(about 0.8 and 1.5 of the knee) are then written into their workload
files by hand; PERF.md records the sweep.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run      # noqa: E402


def half_means(values):
    half = len(values) // 2
    return (float(np.mean(values[:half])) if half else None,
            float(np.mean(values[half:])) if values else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--head", type=float)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    spec = run.resolve(args.workload)
    run.describe_device(spec["chips"])
    ctx = run.Context(spec, seeds[0], args.seconds)
    driver = ctx.plugin("drivers", spec["workload"]["driver"])
    traffic = spec["workload"]["traffic"]
    gen = ctx.plugin("traffic", traffic["generator"])
    mixes = {(rate, seed): gen.generate(
        dict(traffic["params"], rate_per_s=rate), seed, args.seconds,
        spec["config"]["vocab_size"])
        for rate in (float(r) for r in args.rates.split(","))
        for seed in seeds}
    server = driver.Server(ctx)
    server.warm([r for mix in mixes.values() for r in mix], seeds[0])
    for (rate, seed), requests in mixes.items():
        before = driver.histogram_snapshot()
        seen = driver.offer(ctx, server.gw, requests, args.seconds, True)
        after = driver.histogram_snapshot()
        rows = driver.request_rows(seen["t_open"], seen["records"],
                                   args.seconds)
        in_window = [r for r in seen["records"] if r["t_done"] is not None]
        waits = [r["queue_wait_ms"] for r in rows if "queue_wait_ms" in r]
        steps = (after["SERVING_STEP"]["count"]
                 - before["SERVING_STEP"]["count"])
        head = [r for r in rows if args.head and r["due"] < args.head]
        print(json.dumps({
            "rate_per_s": rate, "seed": seed, "sent": len(rows),
            "finished": len(in_window),
            "failed": sum(r["failed"] for r in rows),
            "tokens_per_s_in_window":
                seen["tokens_at_close"] / args.seconds,
            "ttft_ms_p50_p95": [float(np.percentile(
                [r["ttft_due_ms"] for r in rows], q)) for q in (50, 95)],
            "gap_ms_p50_p95": [float(np.percentile(
                [r["gap_ms"] for r in rows if "gap_ms" in r], q))
                for q in (50, 95)],
            **({"head_sent": len(head), "head_ttft_ms_p50_p95": [
                float(np.percentile([r["ttft_due_ms"] for r in head], q))
                for q in (50, 95)]} if head else {}),
            "queued_half_means": half_means(seen["queued"]),
            "queue_wait_ms_half_means": half_means(waits),
            "slots_mean": float(np.mean(seen["slots"])),
            "sched_step_ms": 1e3 * (after["SERVING_STEP"]["sum"]
                                    - before["SERVING_STEP"]["sum"])
            / max(steps, 1),
            "drained_s": max((r["t_done"] for r in in_window),
                             default=0.0) - args.seconds,
        }), flush=True)
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Measure a cell's run-to-run spread as the bounds are set from it:
two sets of runs with the same seeds, each run a process of its own
(this one never touches JAX, so the chip is the child's), and for each
metric the spread of each set: the distance between the first and the
third quartile (``statistics.quantiles(values, n=4)``) over the median.

    python3 benchmarks/tools/spread.py --workload <cell> \\
        --seeds 11,12,13,14,15,16 [--sets 2] [--trace 0] [--out file]
"""
import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seeds = args.seeds.split(",")
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            proc = subprocess.run(
                bench["command"] + ["--workload", args.workload, "--seed",
                                    seed, "--seconds",
                                    str(bench["run_seconds"]), "--trace",
                                    args.trace],
                cwd=ROOT, capture_output=True, text=True)
            last = proc.stdout.strip().splitlines()[-1:] or [""]
            if proc.returncode or not last[0].startswith("{"):
                print(proc.stdout[-3000:], proc.stderr[-3000:], sep="\n")
                raise SystemExit(f"set {k} seed {seed}: exit "
                                 f"{proc.returncode}, no result")
            row = json.loads(last[0])
            rows.append(row)
            print(json.dumps({"set": k, "seed": seed, **row}), flush=True)
            for line in proc.stdout.splitlines():   # numbers and limits
                if line.startswith(("check ", "first tokens", "sender ")):
                    print(f"  seed {seed}: {line}")
        sets.append(rows)
    summary = {}
    for name in sets[0][0]["metrics"]:
        per_set = [[r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]] for rows in sets]
        summary[name] = {
            "medians": [statistics.median(v) for v in per_set],
            # a set's first run in a fresh checkout compiles: set-up is
            # judged without it
            "spreads": [spread(v) for v in per_set if len(v) >= 2],
            "all_correct": all(r["correct"] for rows in sets for r in rows)}
    print("SUMMARY", json.dumps(summary))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"sets": sets, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

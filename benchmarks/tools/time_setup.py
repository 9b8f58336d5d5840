"""Where a cell's set-up goes, and how steadily: the set-up alone (no
window, no reference) in several processes one after another, each
printing the seconds since its start at every mark and, last, what
``setup_s`` would read.

    python3 benchmarks/tools/time_setup.py --workload <cell> \\
        --seeds 1,2,3,4 [--seconds 30]

The first process in a fresh checkout compiles. Prints each process's
marks, then every mark's smallest, median and largest reading over
the processes after the first.
"""
import argparse
import re
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
MARK = re.compile(r"^\[\s*([0-9.]+) s\] (.*)$")


def child(workload: str, seed: int, seconds: float) -> int:
    sys.path.insert(0, str(ROOT))
    from benchmarks import run
    spec = run.resolve(workload)
    run.describe_device(spec["chips"])
    driver = run.Context.plugin("drivers", spec["workload"]["driver"])
    made = driver.set_up(run.Context(spec, seed, seconds))
    end = time.perf_counter()
    run.Context.mark("set-up done")
    setup_s = sum(b - a for a, b, _ in run.setup_spans(end))
    print(f"[{setup_s:7.2f} s] setup_s (less the device runtime's start)")
    close = getattr(made[0], "close", None) or made[0].free
    close()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--child", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.child:
        return child(args.workload, seeds[0], args.seconds)
    runs = []
    for seed in seeds:
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, __file__, "--child", "--workload",
             args.workload, "--seeds", str(seed), "--seconds",
             str(args.seconds)], cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        marks = [(m.group(2), float(m.group(1)))
                 for m in map(MARK.match, proc.stdout.splitlines()) if m]
        if proc.returncode or not marks:
            print(proc.stdout[-3000:], proc.stderr[-3000:], sep="\n")
            raise SystemExit(f"seed {seed}: exit {proc.returncode}")
        print(f"seed {seed}: process {wall:.2f} s; " + "; ".join(
            f"{name} {at:.2f}" for name, at in marks), flush=True)
        runs.append(dict(marks))
    warm = runs[1:] or runs
    for name in runs[0]:
        at = sorted(r[name] for r in warm)
        print(f"{name[:60]:60s} min {at[0]:7.2f} median "
              f"{statistics.median(at):7.2f} max {at[-1]:7.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

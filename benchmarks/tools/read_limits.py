"""Read, on the chip and at a cell's own size, the numbers its limits
are set from: over several seeds in ONE process, what the sound
program gives against the plain reference, and what the control (the
reference in the nearest lower precision) and the faults give.

    python3 benchmarks/tools/read_limits.py --workload <cell> \\
        --seeds 1,2,3 [--seconds 8]

Prints one JSON line a seed, then the largest sound and the smallest
control reading of each number. PERF.md records what was read.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run      # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    spec = run.resolve(args.workload)
    run.describe_device(spec["chips"])
    driver = run.Context.plugin("drivers", spec["workload"]["driver"])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.Context(spec, seed, args.seconds)
        row = driver.readings(ctx)
        rows.append(row)
        print(json.dumps({"seed": seed, **row}), flush=True)
    for who, pick in (("program", max), ("control_fp8", min)):
        names = [k for k, v in rows[0][who].items()
                 if isinstance(v, (int, float))]
        print(who, pick.__name__, {
            k: pick(r[who][k] for r in rows) for k in names})
    return 0


if __name__ == "__main__":
    sys.exit(main())

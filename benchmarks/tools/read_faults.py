"""Read, on the chip and at a cell's own size, what the cell's
comparison makes of a fault injected into the PROGRAM: one run of the
cell through ``run.run_cell`` a fault, in ONE process, each with a seed
of its own. The faults are those a test module of ``benchmarks/tests``
injects at toy size (its ``FAULTS`` and ``HOLES``: functions of a
``pytest.MonkeyPatch``).

    python3 benchmarks/tools/read_faults.py --workload <cell> \\
        --module test_hybrid_ssm_cell --faults a,b --seeds 1,2

Every run prints its ``check`` lines, each number beside its limit;
then one JSON line a fault. PERF.md records what was read.
"""
import argparse
import importlib
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent), str(BENCH / "tests")]

from benchmarks import run      # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--module", required=True)
    ap.add_argument("--faults", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    module = importlib.import_module(args.module)
    known = {**module.FAULTS, **getattr(module, "HOLES", {})}
    faults = args.faults.split(",")
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) != len(faults):
        raise SystemExit("one seed a fault")
    spec = run.resolve(args.workload)
    device = run.describe_device(spec["chips"])
    rows = []
    for fault, seed in zip(faults, seeds):
        run.Context.log(f"== fault {fault}, seed {seed}")
        with pytest.MonkeyPatch.context() as mp:
            known[fault](mp)
            try:
                result = run.run_cell(
                    run.resolve(args.workload), seed, args.seconds, False,
                    device,
                    run.ROOT / ".bench_out" / "trace" / args.workload)
            except Exception as e:     # the faulty program may not fit
                rows.append({"fault": fault, "seed": seed,
                             "error": repr(e)[:400]})
                continue
        rows.append({"fault": fault, "seed": seed,
                     "correct": result["correct"],
                     "metrics": result["metrics"]})
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

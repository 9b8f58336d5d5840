"""The benchmark's own tests: a CPU rehearsal at toy sizes. They go
through ``run.run_cell`` with a device description of their own (never
through ``run.py``'s device path), so nothing here is a device number.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

A toy cell is a real cell of ``BENCHMARK.json`` with its own workload
file and metrics, but with the configuration of the same name under
``toy/configs/`` (toy widths, float32, limits of its own) in the real
one's place and the sizes of ``toy/sizes/<driver>.json`` laid over the
workload's. A cell a later PR adds is rehearsed as it is written; a new
configuration or driver brings a toy file of its name.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TOY = Path(__file__).resolve().parent / "toy"
#: a description for the harness, not a device any number is taken on
REHEARSAL_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def overlay(base: dict, over: dict) -> None:
    for key, value in over.items():
        if isinstance(value, dict):
            overlay(base[key], value)
        else:
            base[key] = value


def toy_spec(cell: str) -> dict:
    from benchmarks import run

    spec = run.resolve(cell)
    workload = spec["workload"]
    spec["config"] = run.load_json(
        TOY / "configs" / f"{workload['config']}.json")
    overlay(workload, run.load_json(
        TOY / "sizes" / f"{workload['driver']}.json"))
    return spec


@pytest.fixture
def toy_cell(tmp_path):
    from benchmarks import run

    def go(cell, seed=2**31 + 11, seconds=2.0, trace=False, rate=None):
        spec = toy_spec(cell)
        if rate:
            spec["workload"]["traffic"]["params"]["rate_per_s"] = rate
        return run.run_cell(spec, seed, seconds, trace, REHEARSAL_DEVICE,
                            tmp_path / "trace")
    return go

"""The data-parallel training cell's pieces at toy size: the driver
through ``ParallelWrapper.fit`` (one worker here, laid over the toy
sizes' ``chips``; four virtual CPU devices when the process has them),
its comparison failing when it should, and the collective reader on a
made-up trace.
"""
import jax
import pytest
from conftest import REHEARSAL_DEVICE, toy_spec

from benchmarks import run
from benchmarks.drivers import train_parallel
from benchmarks.readers import trace_collective

CELL = "resnet50.pw4-b1024"


def test_the_cell_is_the_fit_cell_with_the_call_swapped():
    spec = run.resolve(CELL)
    assert spec["chips"] == spec["workload"]["chips"] == 4
    base = run.resolve("resnet50.fit-b256")
    assert spec["config"] == base["config"]
    mine, theirs = (s["workload"]["traffic"]["params"]
                    for s in (spec, base))
    assert mine == dict(theirs, batch=4 * theirs["batch"])
    reported = {m["name"] for m in spec["end_to_end"]}
    assert reported == {"train_samples_per_s", "setup_s"}


@pytest.mark.parametrize("chips", [1, 4])
def test_toy_cell_is_correct_through_the_wrapper(tmp_path, chips):
    if len(jax.devices()) < chips:
        pytest.skip(f"{chips} devices wanted "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count)")
    spec = toy_spec(CELL)
    spec["workload"]["chips"] = chips
    result = run.run_cell(spec, 2**31 + 11, 1.0, False, REHEARSAL_DEVICE,
                          tmp_path / "trace")
    assert result["correct"] is True, result
    assert result["metrics"]["train_samples_per_s"]["value"] > 0


def test_the_limits_are_the_cell_s_own_and_a_traced_run_reports(tmp_path):
    real = run.resolve(CELL)["workload"]["correct"]
    assert set(real) == {"loss_gap", "grad_trace_gap", "param_change_gap",
                         "window_loss_ratio"}
    assert all(v["limit"] > 0 and v["reason"] for v in real.values())
    spec = toy_spec(CELL)
    result = run.run_cell(spec, 7, 1.0, True, REHEARSAL_DEVICE,
                          tmp_path / "trace")
    assert result["correct"] is True, result
    # the CPU has no device plane; the compile lifecycle is reported
    # (persistent_hit_share needs a cache: a CPU process keeps none)
    assert "compile_s" in result["metrics"]
    spec["workload"]["correct"]["grad_trace_gap"]["limit"] = 0.0
    assert run.run_cell(spec, 7, 1.0, False, REHEARSAL_DEVICE,
                        tmp_path / "t2")["correct"] is False


def test_control_and_partial_batch_are_not_correct():
    ctx = run.Context(toy_spec(CELL), 5, 1.0)
    got = train_parallel.readings(ctx)
    limits = ctx.workload["correct"]
    names = ("loss_gap", "grad_trace_gap", "param_change_gap")
    assert all(got["program"][n] <= limits[n]["limit"] for n in names)
    assert any(got["control_fp8"][n] > limits[n]["limit"] for n in names)
    assert got["fault_partial_batch"]["loss_gap"] > \
        limits["loss_gap"]["limit"]


def test_collective_reader_counts_one_chip_per_step():
    args = {"ops": ["all-reduce"], "module": "^jit_step"}
    dev = {"ops": [["all-reduce.7", 0, 2_000_000],
                   ["all-reduce-start.2", 0, 1_000_000],
                   ["fusion.1", 0, 9_000_000]],
           "modules": [["jit_step(3)", 0, 10_000_000],
                       ["jit_step(3)", 0, 10_000_000],
                       ["jit_norms(4)", 0, 1_000_000]]}
    other = {"ops": [["all-reduce.7", 0, 50_000_000]], "modules": []}
    obs = {"trace": {"devices": [dev, other]}}
    # 3 ms of all-reduce on the first chip over its two steps
    assert trace_collective.read(obs, args) == pytest.approx(1.5)
    quiet = {"trace": {"devices": [dict(dev, ops=dev["ops"][2:])]}}
    assert trace_collective.read(quiet, args) is None
    assert trace_collective.read({"trace": None}, args) is None

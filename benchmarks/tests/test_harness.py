"""The harness end to end on the CPU at toy sizes: the result object
has the contract's keys, the device path fails without a TPU, the
open-loop sender reports how late it ran."""
import json
import subprocess
import sys

import pytest
from conftest import ROOT

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def check_result(result, metrics):
    assert KEYS <= set(result)
    json.dumps(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == set(metrics)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_training_cell_reports_its_end_to_end_metrics(toy_cell):
    check_result(toy_cell("resnet50.fit-b256", seconds=1.0),
                 {"train_samples_per_s", "setup_s"})


def test_serving_cells_report_their_end_to_end_metrics(toy_cell):
    check_result(toy_cell("mistral7b.chat-steady", seconds=3.0),
                 {"gap_p95_ms", "setup_s"})
    check_result(toy_cell("mistral7b.chat-saturated", rate=40.0),
                 {"serve_tokens_per_s", "setup_s"})


def test_traced_run_reports_host_side_layer_metrics(toy_cell):
    # the CPU has no device plane: the trace readers find nothing to
    # read and their metrics are left out, the others are reported
    result = toy_cell("mistral7b.chat-steady", seconds=4.0, trace=True)
    assert result["correct"] is True
    assert {"prefill_ms_p50", "compile_s",
            "sched_step_ms.steady"} <= set(result["metrics"])
    assert not any(name.startswith(("device_", "decode_roofline"))
                   for name in result["metrics"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert result["device"]["window_s"] > 0


def test_the_device_path_fails_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "resnet50.fit-b256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not proc.stdout.strip().startswith("{")


def test_an_unknown_device_kind_is_an_error():
    from benchmarks.trace.peaks import peaks
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        peaks("TPU v9")


def test_a_late_sender_makes_the_run_not_correct(toy_cell, monkeypatch,
                                                 capsys):
    import time

    from deeplearning4j_tpu.serving import ServingGateway
    submit = ServingGateway.submit

    def slow_submit(self, *a, **kw):
        if kw.get("tenant") != "warm-up":
            time.sleep(0.06)    # every send holds the next one up
        return submit(self, *a, **kw)

    monkeypatch.setattr(ServingGateway, "submit", slow_submit)
    result = toy_cell("mistral7b.chat-saturated", seconds=1.0, rate=40.0)
    out = capsys.readouterr().out
    assert "sender ran late by p95" in out
    assert "check send_late_p95_ms" in out and "NOT CORRECT" in out
    assert result["correct"] is False


def test_setup_leaves_out_the_device_runtimes_start(monkeypatch):
    from benchmarks import run
    from benchmarks.readers import wall_span

    def setup_s(end):
        return wall_span.read({"spans": {"set-up": run.setup_spans(end)}},
                              {"span": "set-up", "mode": "seconds"})

    t0 = run.T_START
    monkeypatch.setattr(run, "DEVICE_START", [t0 + 2.0, t0 + 9.0])
    assert setup_s(t0 + 30.0) == pytest.approx(23.0)
    # the tests' runs never look for a chip: nothing to leave out
    monkeypatch.setattr(run, "DEVICE_START", [])
    assert setup_s(t0 + 30.0) == pytest.approx(30.0)

"""The reduction from a trace to device metrics, on a made-up trace
small enough to check by hand and on one recorded on the chip."""
import json
from pathlib import Path

import pytest

from benchmarks.trace import xplane

RECORDED = Path(__file__).resolve().parent / "data" / "trace_resnet.json"


def toy_trace():
    # one device; a loop op (0..100) holding two fusions, then a gap of
    # 50 under "fit-call"/"stage-batch", then a copy (150..170); the
    # last gap (170..200) lies under "fit-call" alone
    ops = [["while.1", 0, 100], ["fusion.1", 0, 40], ["fusion.2", 40, 50],
           ["copy.3", 150, 20], ["fusion.9", 200, 10]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": [["jit_loop(1)", 0, 100],
                                     ["jit_loop(1)", 150, 60],
                                     ["jit_other(2)", 300, 5]]}],
            "host": [["fit-call", 90, 130], ["stage-batch", 100, 40]]}


def test_busy_time_is_the_union_of_op_intervals():
    assert xplane.merged([(0, 10), (5, 20), (30, 40)]) == [[0, 20],
                                                           [30, 40]]
    trace = toy_trace()
    assert xplane.busy(trace["devices"][0]) == [[0, 100], [150, 170],
                                                [200, 210]]
    assert xplane.busy_seconds(trace) == pytest.approx(130e-9)


def test_busy_time_is_averaged_over_the_devices_used():
    trace = toy_trace()
    trace["devices"].append({"name": "/device:TPU:1",
                             "ops": [["fusion.1", 0, 30]],
                             "modules": []})
    trace["devices"].append({"name": "/device:TPU:2", "ops": [],
                             "modules": []})
    assert xplane.busy_seconds(trace) == pytest.approx(80e-9)


def test_self_time_leaves_out_what_nested_ops_cover():
    got = dict(xplane.self_times(toy_trace()["devices"][0]["ops"]))
    assert got["while.1"] == 10 and got["fusion.1"] == 40
    top = xplane.top_ops(toy_trace())
    assert top[0] == ["fusion", pytest.approx(100e-9)]
    assert top[1] == ["copy", pytest.approx(20e-9)]


def test_idle_gaps_go_to_the_shortest_covering_span():
    gaps = dict(xplane.idle_gaps(toy_trace()))
    assert gaps == {"stage-batch": pytest.approx(50e-9),
                    "fit-call": pytest.approx(30e-9)}


def test_module_durations_by_name():
    assert xplane.module_durations(toy_trace(), "^jit_loop") == [
        pytest.approx(100e-9), pytest.approx(60e-9)]
    assert xplane.module_durations(toy_trace(), "^jit_step") == []


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace_reduces_to_sane_numbers():
    trace = json.loads(RECORDED.read_text())
    dev = trace["devices"][0]
    assert dev["ops"] and dev["modules"]
    busy = xplane.busy_seconds(trace)
    span = (max(s + d for _, s, d in dev["ops"])
            - min(s for _, s, _ in dev["ops"])) / 1e9
    assert 0 < busy <= span
    assert sum(ns for _, ns in xplane.self_times(dev["ops"])) / 1e9 \
        == pytest.approx(busy, rel=1e-6)
    assert xplane.top_ops(trace)

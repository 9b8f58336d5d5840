"""The join of the program's records with a device trace, on the CPU:
the trace recorded on the chip (``data/trace_resnet.json``) beside ring
records made up around its one dispatched loop, the clock check, and
the new metrics' files and host-phase readers at toy size. Nothing
here is a device number."""
import json
from pathlib import Path

import pytest
from conftest import ROOT

from benchmarks import run
from benchmarks.trace import timeline

RECORDED = Path(__file__).resolve().parent / "data" / "trace_resnet.json"
FIT = {"top": "ComputationGraph.fit/call", "step": "ComputationGraph.fit",
       "module": "^jit_loop"}
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = [m for m in BENCH["per_layer"] if m["source"] == "program_span"
       and m["name"] != "prefill_ms_p50"]
#: host-phase metrics, read without a device; the rest join the trace
HOST = {"fit_stage_ms_per_step", "sched_host_gap_ms.steady",
        "sched_host_gap_ms.saturated", "prefill_stall_share.steady",
        "prefill_stall_share.saturated", "trace_lower_s",
        "backend_load_s"}


@pytest.fixture
def ring():
    from deeplearning4j_tpu.obs import trace
    trace.reset()
    yield trace
    trace.reset()


def lay_records(trace, t0: float, shift_ms: float = 0.0):
    """Ring records around the recorded trace's one ``jit_loop`` event
    (2113.509 to 2494.083 ms after the profile's start ``t0``): a fit
    call whose group stages for 7 ms, dispatches 0.15 ms before the
    device starts and reads its losses back 0.1 ms after it ends."""
    def at(ms):
        return t0 + (ms + shift_ms) / 1e3

    trace.record("ComputationGraph.fit/etl", at(2101.0), at(2102.0), 8)
    trace.record_phases(
        "ComputationGraph.fit",
        (at(2105.0), at(2106.0), at(2113.359), at(2113.6), at(2494.183)),
        ("prep", "h2d", "dispatch", "sync"), 8, {"steps": 4})
    trace.record("ComputationGraph.fit/listeners", at(2494.2),
                 at(2494.9), 8)
    trace.record("ComputationGraph.fit/call", at(2100.5), at(2495.0), 8)


def recorded_obs(trace, t0: float) -> dict:
    """The driver's ``obs`` for a traced tail of 2100 to 2500 ms."""
    return {"trace": json.loads(RECORDED.read_text()),
            "window": [t0 + 1.0, t0 + 2.5], "trace_window_s": 0.4}


def test_gaps_go_to_the_deepest_covering_phase(ring, monkeypatch, capsys):
    t0 = ring.now() - 10.0          # the profile started ten seconds ago
    monkeypatch.setattr(timeline, "profile_origin_ns",
                        lambda lo, hi: ring.to_epoch_ns(t0))
    lay_records(ring, t0)
    joined = timeline.join(recorded_obs(ring, t0), FIT)
    assert joined is not None and joined["steps"] == 4
    idle = joined["idle"]
    # the device's busy intervals (ms): 2111.103-2111.565 and two short
    # ones to 2113.5 (the stacks), then the loop to 2494.081. Idle: the
    # tail's opening to the fit call (0.5 ms, outside the program), the
    # call's own time around etl and listeners, etl (1 ms), prep (1 ms),
    # staging from 2106 to the first stack at 2111.103 and between
    # the stacks, the launch, and the read-back after the loop
    assert idle[timeline.OUTSIDE] == pytest.approx(0.5e-3 + 5.0e-3,
                                                   abs=2e-5)
    assert idle["ComputationGraph.fit/etl"] == pytest.approx(1e-3,
                                                             abs=1e-5)
    assert idle["ComputationGraph.fit/prep"] == pytest.approx(1e-3,
                                                              abs=1e-5)
    assert idle["ComputationGraph.fit/h2d"] > 5.1e-3
    assert idle["ComputationGraph.fit/call (self)"] == pytest.approx(
        0.5e-3 + 3.0e-3 + (2494.2 - 2494.183) * 1e-3 + 0.1e-3, abs=2e-5)
    assert idle["ComputationGraph.fit/sync"] == pytest.approx(
        (2494.183 - 2494.081) * 1e-3, abs=1e-5)
    assert idle["ComputationGraph.fit/listeners"] == pytest.approx(
        0.7e-3, abs=1e-5)
    # every idle second of the tail is in the table
    busy = sum(e - s for s, e in timeline.xplane.busy(
        recorded_obs(ring, t0)["trace"]["devices"][0])) / 1e9
    assert joined["idle_s"] == pytest.approx(0.4 - busy, abs=2e-5)
    # the anchor lies inside the causal interval (0.1 ms of read-back
    # to 0.15 ms of launch), needs no correction, and all is printed
    check = joined["check"]
    assert check["events"] == 1 and check["broken"] == 0
    assert check["correction_ns"] == 0
    assert check["ceiling_ns"] - check["anchor_ns"] == pytest.approx(
        150e3, abs=20e3)
    assert check["ceiling_ns"] - check["floor_ns"] == pytest.approx(
        250e3, abs=2e3)
    assert abs(joined["drift_ns"]) < 50e3
    out = capsys.readouterr().out
    assert "clock check: 1 ^jit_loop events" in out
    assert "idle by program phase: ComputationGraph.fit/h2d" in out


def test_the_readers_compute_from_the_joined_table(ring, monkeypatch):
    from benchmarks.readers import program_idle
    t0 = ring.now() - 10.0
    monkeypatch.setattr(timeline, "profile_origin_ns",
                        lambda lo, hi: ring.to_epoch_ns(t0))
    lay_records(ring, t0)
    obs = recorded_obs(ring, t0)
    idle = timeline.join(obs, FIT)["idle"]
    # staging the device waits out: idle from the h2d stamp (2106 ms)
    # to the loop's start (2113.509), whatever phase the host is in
    exposed = program_idle.read(obs, {
        "quantity": "phase_to_launch_ms_per_step", "phase": "h2d",
        **FIT})
    assert exposed == pytest.approx(1e3 * (
        idle["ComputationGraph.fit/h2d"]
        + idle["ComputationGraph.fit/dispatch"]) / 4, abs=5e-3)
    assert exposed > 1e3 * idle["ComputationGraph.fit/h2d"] / 4
    named = program_idle.read(obs, {"quantity": "named_share", **FIT})
    unnamed = idle[timeline.OUTSIDE] \
        + idle["ComputationGraph.fit/call (self)"]
    assert named == pytest.approx(
        100.0 * (1 - unnamed / sum(idle.values())))
    assert 0 < named < 100


def test_an_anchor_off_by_5_ms_fails_the_check(ring, monkeypatch, capsys):
    t0 = ring.now() - 10.0
    monkeypatch.setattr(timeline, "profile_origin_ns",
                        lambda lo, hi: ring.to_epoch_ns(t0) + 5_000_000)
    lay_records(ring, t0)
    from benchmarks.readers import program_idle
    obs = recorded_obs(ring, t0)
    assert timeline.join(obs, FIT) is None
    assert program_idle.read(obs, {"quantity": "named_share",
                                   **FIT}) is None
    out = capsys.readouterr().out
    assert "clock check FAILED" in out
    assert "corrected by 49" in out and "limit 2000 us" in out


def test_an_anchor_just_outside_is_moved_into_the_causal_interval(
        ring, monkeypatch):
    # the device planes sit within a millisecond of the profile's
    # start, not on it (chip run 1, PR 24): 0.4 ms off is corrected
    t0 = ring.now() - 10.0
    monkeypatch.setattr(timeline, "profile_origin_ns",
                        lambda lo, hi: ring.to_epoch_ns(t0) + 400_000)
    lay_records(ring, t0)
    joined = timeline.join(recorded_obs(ring, t0), FIT)
    check = joined["check"]
    assert check["correction_ns"] == pytest.approx(300e3, abs=20e3)
    assert check["offset_ns"] == check["floor_ns"]
    assert check["broken"] == 0


def test_without_the_ring_or_a_device_the_readers_read_nothing(
        ring, monkeypatch):
    from benchmarks.readers import (compile_phases, program_idle,
                                    program_phase)
    obs = {"window": [0.0, 1.0], "setup_end": 0.0, "trace_window_s": 0.1,
           "spans": {"set-up": [[0.0, 0.0, 1]]},
           "trace": {"devices": [], "host": []}}
    # no device plane (the CPU): the joined readers leave theirs out
    assert program_idle.read(obs, {"quantity": "named_share",
                                   **FIT}) is None
    # a program without the ring (a parent commit): all leave theirs out
    monkeypatch.setattr(timeline, "program", lambda: None)
    assert program_phase.read(obs, {"quantity": "host_gap_ms",
                                    "record": "x", "between": "y"}) is None
    assert compile_phases.read(obs, {"phases": ["jaxpr_trace"]}) is None
    assert program_idle.read(obs, {"quantity": "named_share",
                                   **FIT}) is None


def test_a_ring_that_overwrote_the_window_is_refused(ring):
    from benchmarks.readers import program_phase
    ring.enable(ring=4)
    ring.disable()
    t_open = ring.now()
    for _ in range(9):
        t = ring.now()
        ring.record_phases("s", (t, t, t, ring.now()),
                           ("h2d", "dispatch", "sync"), None, {"steps": 1})
    with pytest.raises(LookupError):
        program_phase.read({"window": [t_open, ring.now()]}, {
            "quantity": "phase_ms_per_step", "record": "s",
            "phase": "h2d"})


def test_host_gap_skips_steps_with_an_admission_between(ring):
    from benchmarks.readers import program_phase
    phases = ("h2d", "dispatch", "sync", "deliver")

    def step(t):        # sync ends 30 ms in, delivery takes 1 ms
        ring.record_phases("serving.decode_step",
                           (t, t + 1e-3, t + 2e-3, t + 30e-3, t + 31e-3),
                           phases, 1, {"active": 2})

    step(100.0)
    step(100.033)                   # 3 ms sync end -> 4 ms: gap 4 ms
    ring.record_phases("serving.prefill",
                       (100.07, 100.071, 100.072, 100.08),
                       phases[:3], 2, {"rid": 1})
    step(100.09)                    # an admission between: left out
    step(100.123)                   # gap 4 ms again
    ring.record("serving.loop/admit", 100.064, 100.0801, 2, admitted=1,
                active=2)
    ring.record("serving.loop/admit", 100.2, 100.3, 3, admitted=1,
                active=0)           # nothing was decoding: no stall
    obs = {"window": [99.0, 101.0]}
    assert program_phase.read(obs, {
        "quantity": "host_gap_ms", "record": "serving.decode_step",
        "between": "serving.prefill"}) == pytest.approx(4.0)
    assert program_phase.read(obs, {
        "quantity": "share_of_window", "record": "serving.loop/admit",
        "where": "active"}) == pytest.approx(100 * 0.0161 / 2.0)


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_every_new_metric_names_a_reader_that_exists(metric):
    spec = run.load_json(ROOT / "benchmarks" / "metrics"
                         / f"{metric['name']}.json")
    assert spec["name"] == metric["name"]
    reader = run.Context.plugin("readers", spec["reader"])
    assert callable(reader.read)
    assert metric["workloads"], "each new metric lists its cells"
    # none is taken for a device number by the CPU rehearsal
    assert not metric["name"].startswith(("device_", "decode_roofline"))
    assert len(NEW) == 11


@pytest.mark.parametrize("cell, kwargs", [
    ("resnet50.fit-b256", {"seconds": 1.0}),
    ("mistral7b.chat-steady", {"seconds": 3.0}),
    ("mistral7b.chat-saturated", {"rate": 40.0}),
])
def test_each_toy_cell_yields_its_host_phase_metrics(toy_cell, cell,
                                                     kwargs):
    result = toy_cell(cell, trace=True, **kwargs)
    assert result["correct"] is True
    mine = {m["name"] for m in NEW if cell in m["workloads"]}
    got = set(result["metrics"])
    # host phases are read on the CPU; what joins a device trace is
    # left out here, as test_harness expects of the trace readers
    assert mine & HOST <= got
    assert not (mine - HOST) & got
    for name in mine & HOST:
        value = result["metrics"][name]["value"]
        assert value > 0 and value == value
        if name.startswith("prefill_stall_share"):
            assert value < 100

"""Telemetry spine (obs/): span tracing, metrics registry, worker
health — including the PR acceptance criteria: a 10-step fit under
tracing yields Chrome-trace JSONL whose spans cover >= 95% of wall
time with ETL/step/sync attribution; /metrics exposes step-latency
histograms plus sentry retrace counters in valid Prometheus text; and
tracing disabled builds ZERO Chrome events on the step path, which
pays one ring record a step (under 1 µs) and far under 1% of a
bench-class step in all.
"""
import json
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.data.iterators import AsyncDataSetIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork, \
    NeuralNetConfiguration
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.obs import health, metrics, trace


def _net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Adam(learning_rate=0.01))
            .list()
            .layer(DenseLayer(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(n=10, b=8, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.standard_normal((b, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
        out.append(DataSet(x, y))
    return out


@pytest.fixture(autouse=True)
def _tracer_off_after():
    yield
    trace.reset()


# --- tracer -----------------------------------------------------------------

def test_span_nesting_roundtrips_through_jsonl(tmp_path):
    path = tmp_path / "t.jsonl"
    trace.enable(str(path))
    trace.set_thread_name("main-test")
    with obs.span("outer", {"k": 1}):
        with obs.span("inner"):
            pass
    t0 = obs.now()
    trace.add_span("explicit", t0, t0 + 0.5)    # explicit t0/t1 API
    trace.instant("marker")
    trace.disable()
    evs = trace.read_trace(str(path))
    by_name = {e["name"]: e for e in evs}
    # thread metadata carries the worker label
    assert by_name["thread_name"]["args"]["name"] == "main-test"
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["tid"] == inner["tid"]
    # nesting: inner's interval contained in outer's (how Chrome/
    # Perfetto nest spans of one tid)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["args"] == {"k": 1}
    assert by_name["explicit"]["dur"] == pytest.approx(5e5, rel=1e-3)
    assert by_name["marker"]["ph"] == "i"
    # the file itself is Chrome "JSON array format": starts with [
    assert path.read_text().startswith("[\n")


def test_ring_buffer_bounds_memory(tmp_path):
    trace.enable(str(tmp_path / "r.jsonl"), ring=8)
    t0 = obs.now()
    for i in range(50):
        trace.add_span(f"s{i}", t0, t0 + 1e-6)
    assert len(trace.events()) <= 8
    assert trace.events_recorded() == 50
    # the FILE keeps everything the ring dropped
    trace.disable()
    assert sum(e.get("ph") == "X"
               for e in trace.read_trace(trace.trace_path())) == 50


def test_tracing_disabled_records_nothing_on_step_path():
    trace.reset()
    base = trace.events_recorded()
    net = _net()
    net.fit(ListDataSetIterator(_batches(3)))
    with obs.span("should-not-record"):
        pass
    t0 = obs.now()
    trace.add_span("also-not", t0, t0)
    # nothing exported while disabled: no Chrome event built, no file
    assert trace.events_recorded() == base == 0
    assert trace.trace_path() is None and not trace.enabled()
    # the ring is always on: exactly ONE record a step, its phases
    # names and not spans of their own; the gated span API adds none
    recs = trace.records()
    steps = [r for r in recs if r.name == "MultiLayerNetwork.fit"]
    assert len(steps) == 3
    assert all(r.phases == ("h2d", "dispatch", "sync")
               and len(r.stamps) == 4 for r in steps)
    names = {r.name for r in recs}
    assert "MultiLayerNetwork.fit/etl" in names
    assert not names & {"should-not-record", "also-not",
                        "MultiLayerNetwork.fit/step",
                        "MultiLayerNetwork.fit/h2d"}
    assert trace.dropped() == 0


def test_off_path_overhead_under_one_percent_of_bench_step():
    # bench.py computes this against the measured ResNet step; here the
    # same probe is held to <1% of a conservative 5 ms step (the real
    # bench step is far larger)
    # min of 3 probes: the measurement itself is µs-scale and a busy
    # box can inflate any single run
    reps = [obs.overhead_report(step_seconds=0.005, iters=500)
            for _ in range(3)]
    rep = min(reps, key=lambda r: r["off_path_cost_us"])
    assert rep["tracing"] is False
    assert rep["off_path_cost_us"] < 50.0
    assert rep["overhead_pct_of_step"] < 1.0
    # the always-on part: one step record is one ring append
    assert min(r["ring_append_us"] for r in reps) < 1.0
    # the probe scrubs its synthetic samples from the live registry
    # and the process's ring
    assert "obs_overhead_probe" not in metrics.step_summary()
    assert "obs_overhead_probe" not in str(
        metrics.STEPS.snapshot())
    assert not any(r.name.startswith("obs_overhead_probe")
                   for r in trace.records())


# --- the always-on ring: clock, overwrites, export, causes ------------------

@pytest.mark.parametrize("fresh", [False, True])
def test_anchor_maps_a_stamp_to_epoch_ns_and_back(fresh):
    import time
    anchor = trace.clock() if fresh else None
    t, wall = obs.now(), time.time_ns()
    ns = trace.to_epoch_ns(t, anchor)
    assert abs(ns - wall) < 50_000          # within 50 µs of the wall
    assert abs(trace.from_epoch_ns(ns, anchor) - t) < 50e-6
    a = trace.clock()
    assert a.width_s < 50e-6 and a.epoch_ns > 0


def test_small_ring_reports_dropped_and_refuses_the_window():
    trace.enable(ring=8)
    trace.disable()
    t_open = obs.now()
    for i in range(20):
        t = obs.now()
        obs.record_step("tiny", t, t, t, obs.now())
    assert len(trace.records()) == 8
    assert trace.dropped() == 12
    assert obs.report(spans=1)["trace"]["records_dropped"] == 12
    # the window's start was overwritten: the reader's helper refuses
    with pytest.raises(LookupError, match="DL4J_TPU_TRACE_RING"):
        trace.records(since=t_open)
    # a window that begins after the oldest survivor is served
    kept = trace.records()
    assert trace.records(since=kept[3].stamps[-1])[0].seq == kept[3].seq


@pytest.mark.parametrize("entry, call, names", [
    ("E", lambda t: obs.record_step("E", t, t + 1, t + 2, t + 3,
                                    {"steps": 2}),
     ["E/step", "E/h2d", "E/dispatch", "E/sync"]),
    ("E", lambda t: obs.record_step("E", t, t + 1, t + 2, t + 3,
                                    start=t - 1, end=t + 4),
     ["E/step", "E/prep", "E/h2d", "E/dispatch", "E/sync",
      "E/deliver"]),
    ("E", lambda t: obs.record_etl("E", t, t + 1), ["E/etl"]),
    ("ParallelWrapper.fit",
     lambda t: obs.record_worker_step("w0", t, t + 1, t + 2, t + 3,
                                      nbytes=64, staged_ahead=True,
                                      ahead=True),
     ["ParallelWrapper.fit/step", "ParallelWrapper.fit/h2d",
      "ParallelWrapper.fit/dispatch",
      "ParallelWrapper.fit/collective_sync"]),
])
def test_export_expands_one_record_into_the_old_event_names(
        tmp_path, entry, call, names):
    # the JSONL keeps, name for name and in order, what the four-way
    # add_span fan-out wrote before; on demand from the ring likewise
    path = tmp_path / "e.jsonl"
    trace.enable(str(path))
    call(100.0)
    trace.disable()
    assert len(trace.records()) == 1
    try:
        written = [e for e in trace.read_trace(str(path))
                   if e["ph"] == "X"]
        assert [e["name"] for e in written] == names
        assert [e["name"] for e in trace.events()] == names
        whole = written[0]
        assert sum(e["dur"] for e in written[1:]) in (
            0, pytest.approx(whole["dur"]))
        meta = {e["name"]: e for e in trace.read_trace(str(path))
                if e["ph"] == "M"}
        assert meta["clock_anchor"]["args"]["epoch_ns"] > 0
    finally:
        metrics.drop_entry("E")


def test_sentried_first_call_writes_compile_records_caused_by_it():
    import jax.numpy as jnp
    from deeplearning4j_tpu.perf import sentry
    fn = sentry.jit(lambda x: jnp.tanh(x) * 3.0 + x,
                    name="probe.compile_cause")
    x = jnp.ones((5, 7))     # made before: its own compiles are eager

    def mine():
        return [r for r in trace.records()
                if r.name.startswith("compile/")
                and r.cause == "probe.compile_cause"]

    fn(x)
    first = mine()
    assert {"compile/jaxpr_trace", "compile/jaxpr_to_mlir",
            "compile/backend_compile"} <= {r.name for r in first}
    assert all(r.stamps[0] <= r.stamps[1] for r in first)
    snap = fn.stats.snapshot()
    assert snap["jaxpr_trace_s"] > 0 and snap["backend_compile_s"] > 0
    assert snap["compile_time_s"] >= snap["backend_compile_s"]
    fn(x)
    assert len(mine()) == len(first)        # the second call: none
    # what no sentried function asked for is caused by "eager"
    jnp.ones((3, 11)) + 1.0
    assert any(r.cause == "eager" for r in trace.records()
               if r.name.startswith("compile/"))


# --- the acceptance fit: 10 steps, traced -----------------------------------

def _coverage(spans):
    """Union coverage of [ts, ts+dur) over traced wall time."""
    spans = sorted(spans, key=lambda e: (e["ts"], -e["dur"]))
    wall = (max(e["ts"] + e["dur"] for e in spans)
            - min(e["ts"] for e in spans))
    covered = end = 0.0
    for e in spans:
        s, d = e["ts"], e["dur"]
        if s + d <= end:
            continue
        covered += (s + d) - max(s, end)
        end = s + d
    return covered / wall


def test_ten_step_fit_trace_covers_wall_time(tmp_path):
    path = tmp_path / "fit.jsonl"
    trace.enable(str(path))
    from deeplearning4j_tpu.train.listeners import ScoreIterationListener
    net = _net()
    net.set_listeners(ScoreIterationListener(5))
    net.fit(ListDataSetIterator(_batches(10)))
    trace.disable()
    evs = [e for e in trace.read_trace(str(path)) if e.get("ph") == "X"]
    names = {e["name"] for e in evs}
    # ETL / step / sync attribution present
    assert "MultiLayerNetwork.fit/etl" in names
    assert "MultiLayerNetwork.fit/step" in names
    assert "MultiLayerNetwork.fit/sync" in names
    assert "MultiLayerNetwork.fit/h2d" in names
    assert "MultiLayerNetwork.fit/dispatch" in names
    steps = [e for e in evs if e["name"] == "MultiLayerNetwork.fit/step"]
    assert len(steps) == 10
    # phases nest inside their step span
    syncs = sorted((e for e in evs
                    if e["name"] == "MultiLayerNetwork.fit/sync"),
                   key=lambda e: e["ts"])
    st = sorted(steps, key=lambda e: e["ts"])
    for s, sy in zip(st, syncs):
        assert s["ts"] <= sy["ts"] + 1e-3
        assert sy["ts"] + sy["dur"] <= s["ts"] + s["dur"] + 1e-3
    # >= 95% of traced wall time attributed (acceptance criterion)
    top = [e for e in evs if e["name"] in (
        "MultiLayerNetwork.fit/step", "MultiLayerNetwork.fit/etl",
        "MultiLayerNetwork.fit/listeners")]
    assert _coverage(top) >= 0.95


def test_env_gated_trace_end_to_end(tmp_path):
    """The acceptance path verbatim: a 10-step MultiLayerNetwork.fit
    in a fresh process with DL4J_TPU_TRACE set produces Chrome-trace
    JSONL covering >= 95% of wall time, and the same process's
    /metrics exposition carries the step histogram + retrace
    counters."""
    import os
    import subprocess
    import sys
    path = tmp_path / "env.jsonl"
    prog = """
import numpy as np
from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
conf = (NeuralNetConfiguration.builder().seed(7)
        .updater(upd.Adam(learning_rate=0.01)).list()
        .layer(DenseLayer(n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(4)).build())
net = MultiLayerNetwork(conf).init()
rng = np.random.default_rng(0)
data = [DataSet(rng.standard_normal((8, 4)).astype(np.float32),
                np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)])
        for _ in range(10)]
net.fit(ListDataSetIterator(data))
from deeplearning4j_tpu.obs import metrics, trace
trace.flush()
print(metrics.REGISTRY.exposition())
"""
    env = dict(os.environ, DL4J_TPU_TRACE=str(path),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    # the child's /metrics content: step histogram + sentry counters
    fams = metrics.parse_exposition(
        "\n".join(ln for ln in r.stdout.splitlines()
                  if ln.startswith(("#", "dl4j_tpu_"))))
    entry = (("entry", "MultiLayerNetwork.fit"),)
    assert fams[("dl4j_tpu_step_latency_seconds_count", entry)] == 10
    assert fams[("dl4j_tpu_retrace_traces_total",
                 (("function", "MultiLayerNetwork.train_step"),))] >= 1
    # the trace file covers >= 95% of its wall time with attribution
    evs = [e for e in trace.read_trace(str(path))
           if e.get("ph") == "X"]
    top = [e for e in evs if e["name"] in (
        "MultiLayerNetwork.fit/step", "MultiLayerNetwork.fit/etl")]
    assert sum(e["name"].endswith("/step") for e in top) == 10
    assert {e["name"] for e in evs} >= {
        "MultiLayerNetwork.fit/etl", "MultiLayerNetwork.fit/step",
        "MultiLayerNetwork.fit/h2d", "MultiLayerNetwork.fit/dispatch",
        "MultiLayerNetwork.fit/sync"}
    assert _coverage(top) >= 0.95


def test_xprof_summary_reads_obs_trace(tmp_path):
    path = tmp_path / "fit.jsonl"
    trace.enable(str(path))
    net = _net()
    net.fit(ListDataSetIterator(_batches(4)))
    trace.disable()
    import sys
    sys.path.insert(0, "tools")
    import xprof_summary
    out = xprof_summary.summarize_obs(str(path))
    assert "MultiLayerNetwork.fit/step" in out
    assert "covered by spans" in out.splitlines()[1]


# --- metrics registry + exposition ------------------------------------------

def test_metrics_exposition_is_valid_prometheus_text():
    net = _net()
    net.fit(ListDataSetIterator(_batches(3)))
    text = metrics.REGISTRY.exposition()
    # parse_exposition raises on any malformed sample line
    fams = metrics.parse_exposition(text)
    # step-latency histogram for the fit entry point
    entry = (("entry", "MultiLayerNetwork.fit"),)
    inf_key = ("dl4j_tpu_step_latency_seconds_bucket",
               (("entry", "MultiLayerNetwork.fit"), ("le", "+Inf")))
    assert inf_key in fams
    count = fams[("dl4j_tpu_step_latency_seconds_count", entry)]
    assert fams[inf_key] == count >= 3
    assert fams[("dl4j_tpu_step_latency_seconds_sum", entry)] > 0
    # histogram buckets are cumulative (monotone nondecreasing in le)
    buckets = sorted(
        ((float("inf") if dict(k[1])["le"] == "+Inf"
          else float(dict(k[1])["le"])), v)
        for k, v in fams.items()
        if k[0] == "dl4j_tpu_step_latency_seconds_bucket"
        and dict(k[1]).get("entry") == "MultiLayerNetwork.fit")
    assert all(a[1] <= b[1] for a, b in zip(buckets, buckets[1:]))
    # sentry retrace + compile-cache families are first-class
    assert ("dl4j_tpu_retrace_traces_total",
            (("function", "MultiLayerNetwork.train_step"),)) in fams
    assert any(k[0] == "dl4j_tpu_compile_cache_requests_total"
               for k in fams)
    assert any(k[0] == "dl4j_tpu_compile_time_seconds_total"
               for k in fams)
    # TYPE lines present for the histogram family
    assert "# TYPE dl4j_tpu_step_latency_seconds histogram" in text


def test_metrics_server_and_healthz_endpoint():
    health.reset()
    srv = metrics.MetricsServer(port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/metrics") as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            metrics.parse_exposition(r.read().decode())
        with urllib.request.urlopen(base + "/healthz") as r:
            h = json.loads(r.read().decode())
        assert h["status"] == "ok" and h["stale_workers"] == []
        # a deliberately-stalled worker flips /healthz to 503
        health.heartbeat("w-stalled", t=obs.now() - 1e4)
        health.heartbeat("w-live")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz")
        assert ei.value.code == 503
        body = json.loads(ei.value.read().decode())
        assert body["stale_workers"] == ["w-stalled"]
    finally:
        srv.stop()
        health.reset()


def test_registry_reset_keeps_standing_family_handles():
    reg = metrics.MetricsRegistry()
    fam = reg.histogram("t_steps", "probe", ("entry",))
    fam.labels(entry="a").observe(0.1)
    reg.reset()
    assert "t_steps" in reg.exposition()     # family survives reset
    fam.labels(entry="a").observe(0.2)       # old handle still works
    assert '{entry="a"}' in str(reg.snapshot()["t_steps"]["values"])
    assert reg.snapshot()["t_steps"]["values"]['{entry="a"}'][
        "count"] == 1                        # pre-reset sample gone


def test_heartbeat_retire_clears_finished_worker():
    health.reset()
    health.heartbeat("done-worker", t=obs.now() - 1e4)
    assert health.stale_workers(stale_after=30) == ["done-worker"]
    health.retire("done-worker")             # normal loop completion
    assert health.check() == {}              # no permanent false alarm
    health.retire("never-registered")        # idempotent


def _watch_lines(capsys):
    """The watcher prints one JSON line per sample."""
    return [json.loads(ln) for ln in
            capsys.readouterr().out.splitlines() if ln.startswith("{")]


def test_tpu_watch_captures_healthz_503_body(capsys):
    import sys
    sys.path.insert(0, "tools")
    import tpu_watch
    health.reset()
    health.heartbeat("w-stuck", t=obs.now() - 1e4)
    srv = metrics.MetricsServer(port=0).start()
    try:
        tpu_watch._scrape_telemetry(
            None, f"http://127.0.0.1:{srv.port}/healthz", None)
    finally:
        srv.stop()
        health.reset()
    (rec,) = [r for r in _watch_lines(capsys)
              if r["event"] == "healthz"]
    # the 503 body — naming the stale worker — must be captured, not
    # swallowed as an HTTPError
    assert rec["status"] == 503
    assert rec["body"]["stale_workers"] == ["w-stuck"]


def test_tpu_watch_trace_tail_is_incremental(tmp_path, capsys):
    import sys
    sys.path.insert(0, "tools")
    import tpu_watch
    tpu_watch._TRACE_POS.clear()
    tpu_watch._SPAN_TOTALS.clear()
    path = tmp_path / "t.jsonl"
    trace.enable(str(path))
    t0 = obs.now()
    trace.add_span("a", t0, t0 + 0.001)
    trace.flush()
    tpu_watch._scrape_telemetry(None, None, str(path))
    off1, _ = tpu_watch._TRACE_POS[str(path)]
    trace.add_span("a", t0, t0 + 0.002)
    trace.flush()
    tpu_watch._scrape_telemetry(None, None, str(path))
    off2, _ = tpu_watch._TRACE_POS[str(path)]
    trace.disable()
    assert off2 > off1 > 0                    # only the tail is re-read
    assert tpu_watch._SPAN_TOTALS["a"] == pytest.approx(3000, rel=0.01)
    recs = _watch_lines(capsys)
    assert recs[-1]["top_spans_ms"]["a"] == pytest.approx(3.0,
                                                          rel=0.01)


def test_stale_worker_detector_explicit_clock():
    health.reset()
    now = obs.now()
    health.heartbeat("a", t=now - 5)
    health.heartbeat("b", t=now - 100)
    chk = health.check(stale_after=30, now=now)
    assert not chk["a"]["stale"] and chk["b"]["stale"]
    assert health.stale_workers(stale_after=30, now=now) == ["b"]
    assert chk["b"]["age_s"] == pytest.approx(100, abs=1)
    health.reset()


# --- instrumented subsystems ------------------------------------------------

def test_worker_step_recording_and_heartbeat(tmp_path):
    """record_worker_step (the ParallelWrapper.fit per-step call):
    latency histogram + collective-sync counter + heartbeat + spans."""
    health.reset()
    trace.enable(str(tmp_path / "w.jsonl"))
    before = metrics.WORKER_STEP.labels(worker="procX").count
    ahead = metrics.WORKER_STAGED_AHEAD.labels(worker="procX").value
    launched = metrics.WORKER_AHEAD.labels(worker="procX").value
    t0 = obs.now()
    for staged_ahead, step_ahead in ((False, False), (True, False),
                                     (True, True)):
        obs.record_worker_step("procX", t0, t0 + 0.001, t0 + 0.002,
                               t0 + 0.010, nbytes=4096,
                               staged_ahead=staged_ahead,
                               ahead=step_ahead)
    trace.disable()
    assert metrics.WORKER_STEP.labels(worker="procX").count \
        == before + 3
    assert metrics.WORKER_SYNC.labels(worker="procX").value > 0
    # the engagement share's numerator: steps whose batch was staged
    # during the step before (over WORKER_STEP's count)
    assert metrics.WORKER_STAGED_AHEAD.labels(worker="procX").value \
        == ahead + 2
    # ... and steps launched before their predecessor's loss was read
    assert metrics.WORKER_AHEAD.labels(worker="procX").value \
        == launched + 1
    recs = [r for r in trace.records()
            if r.name == "ParallelWrapper.fit"][-3:]
    assert [r.phases for r in recs] == [
        ("h2d", "dispatch", "collective_sync")] * 3
    assert [r.counts for r in recs] == [
        {"worker": "procX", "bytes": 4096, "staged_ahead": s, "ahead": a}
        for s, a in ((0, 0), (1, 0), (1, 1))]
    assert not health.check(stale_after=30)["procX"]["stale"]
    names = {e["name"] for e in trace.events()}
    assert "ParallelWrapper.fit/step" in names
    assert "ParallelWrapper.fit/collective_sync" in names
    health.reset()


def test_worker_pipeline_counters_are_exported():
    """Both engagement counters of the wrapper's loop reach /metrics
    as declared families, a sample a worker, and the runbook names
    them; the heartbeat reads what it read."""
    import os
    health.reset()
    t0 = obs.now()
    obs.record_worker_step("procY", t0, t0 + 0.001, t0 + 0.002,
                           t0 + 0.010, nbytes=8, staged_ahead=True,
                           ahead=True)
    text = metrics.REGISTRY.exposition()
    samples = metrics.parse_exposition(text)
    for name in ("dl4j_tpu_worker_staged_ahead_total",
                 "dl4j_tpu_worker_steps_ahead_total"):
        assert metrics.FAMILIES[name] == "counter"
        assert f"# TYPE {name} counter" in text
        assert samples[(name, (("worker", "procY"),))] >= 1
    ops = open(os.path.join(os.path.dirname(__file__), os.pardir,
                            "docs", "OPS.md")).read()
    assert "dl4j_tpu_worker_steps_ahead_total" in ops
    assert set(health.check(stale_after=30)) == {"procY"}
    health.reset()


def test_parallel_wrapper_heartbeat_flags_stalled_worker():
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    try:
        from deeplearning4j_tpu.parallel import ParallelWrapper
    except ImportError:
        # this jaxlib lacks jax.shard_map: the parallel subsystem is
        # unimportable here (pre-existing, see tests/test_parallel.py)
        pytest.skip("parallel subsystem unimportable on this jax")
    health.reset()
    net = _net()
    w = ParallelWrapper.builder(net).workers(8).build()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
    before = metrics.WORKER_STEP.labels(worker="proc0").count
    w.fit(ListDataSetIterator(DataSet(x, y), batch_size=16), epochs=1)
    # the fit loop heart-beat once per step and timed every worker step
    assert metrics.WORKER_STEP.labels(worker="proc0").count \
        - before >= 4
    # normal completion RETIRES the beat (PR 2 review fix: a finished
    # fit must not read as a permanently stale worker in a
    # train-then-serve process); only a crashed loop leaves one behind
    chk = health.check(stale_after=30)
    assert "proc0" not in chk
    # a worker that stops beating (stalled collective) gets flagged
    health.heartbeat("proc1", t=obs.now() - 1e3)
    assert health.stale_workers(stale_after=30) == ["proc1"]
    health.reset()


def test_async_iterator_feeds_etl_metrics():
    before = metrics.PREFETCH_WAIT._children[()].value
    it = AsyncDataSetIterator(ListDataSetIterator(_batches(5)),
                              queue_size=2)
    n = sum(1 for _ in it)
    assert n == 5
    assert it.etl_wait_seconds > 0
    assert metrics.PREFETCH_WAIT._children[()].value > before


def test_parallel_inference_queue_and_latency_metrics():
    try:
        from deeplearning4j_tpu.parallel.inference import \
            ParallelInference
    except ImportError:
        # parallel package __init__ needs jax.shard_map (pre-existing
        # import failure on this jaxlib, see tests/test_parallel.py)
        pytest.skip("parallel subsystem unimportable on this jax")
    net = _net()
    reqs0 = metrics.INFER_REQS._children[()].value
    lat0 = metrics.INFER_LATENCY._children[()].count
    pi = ParallelInference(net, batch_limit=8, buckets=(1, 2, 4, 8))
    try:
        out = pi.output(np.zeros((2, 4), np.float32))
        assert out.shape == (2, 2)
    finally:
        pi.shutdown()
    assert metrics.INFER_REQS._children[()].value == reqs0 + 1
    assert metrics.INFER_LATENCY._children[()].count == lat0 + 1
    assert metrics.INFER_BATCH._children[()].count >= 1


# --- merged report + consumers ----------------------------------------------

def test_report_merges_trace_metrics_health(tmp_path):
    trace.enable(str(tmp_path / "r.jsonl"))
    t0 = obs.now()
    trace.add_span("probe", t0, t0 + 0.001)
    rep = obs.report(spans=5)
    assert rep["trace"]["enabled"] is True
    assert rep["trace"]["events_recorded"] >= 1
    assert any(e.get("name") == "probe" for e in rep["spans"])
    assert "dl4j_tpu_step_latency_seconds" in rep["metrics"]
    assert isinstance(rep["health"], dict)
    json.dumps(rep)            # snapshot must be JSON-serializable


def test_crash_dump_carries_compile_and_obs_state():
    from deeplearning4j_tpu.utils import crashreport
    net = _net()
    # DL4J_TPU_TRACE unset: the dump still holds the last steps
    assert not trace.enabled()
    net.fit(ListDataSetIterator(_batches(2)))
    report = crashreport.generate_memory_status_report(net)
    assert "MultiLayerNetwork.fit/step" in report
    assert "MultiLayerNetwork.fit/sync" in report
    assert "compile subsystem (perf.compile_report)" in report
    assert "telemetry (obs.report" in report
    assert "compile_time_s" in report
    assert "dl4j_tpu_step_latency_seconds" in report


def test_stats_listener_records_obs_summary():
    from deeplearning4j_tpu.train.stats import (InMemoryStatsStorage,
                                                StatsListener)
    storage = InMemoryStatsStorage()
    net = _net()
    net.set_listeners(StatsListener(storage, frequency=1,
                                    session_id="obs_test"))
    net.fit(ListDataSetIterator(_batches(3)))
    recs = storage.get_records("obs_test")
    assert recs
    ob = recs[-1]["obs"]
    assert ob["tracing"] is False
    assert "MultiLayerNetwork.fit" in ob["step"]
    assert ob["step"]["MultiLayerNetwork.fit"]["count"] >= 3


def test_score_listener_logs_step_loss_not_extra_score():
    from deeplearning4j_tpu.train.listeners import (
        CollectScoresListener, ScoreIterationListener)

    class FakeNet:
        score_ = 0.125

        def score(self, dataset=None):
            raise AssertionError(
                "listener must not call net.score() per iteration "
                "(extra device sync)")

    net = FakeNet()
    ScoreIterationListener(1).iteration_done(net, 10, 0)
    c = CollectScoresListener()
    c.iteration_done(net, 1, 0)
    assert c.scores == [(1, 0.125)]

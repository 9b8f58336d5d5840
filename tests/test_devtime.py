"""Device-time observatory (obs/devtime.py — ARCHITECTURE.md §16).

Fences: the xplane wire parser reads real captures, the HLO scope map
attributes forward AND backward ops to their layers, the roofline
math is exact, the gap report carries exactly GAP_KEYS ranked by
share, an instrumented smoke fit attributes EVERY layer type in the
net, and — the PR 2 contract — with ``DL4J_TPU_DEVTIME`` unset the
fit loops run zero profiler sessions and zero captures
(counter-asserted).
"""
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deeplearning4j_tpu.nn import (MultiLayerNetwork,  # noqa: E402
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.config import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer,  # noqa: E402
                                          DenseLayer, OutputLayer,
                                          SubsamplingLayer)
from deeplearning4j_tpu.nn import updaters as upd  # noqa: E402
from deeplearning4j_tpu.obs import devtime  # noqa: E402
from deeplearning4j_tpu.obs import metrics as obs_metrics  # noqa: E402
from deeplearning4j_tpu.perf import sentry  # noqa: E402
from deeplearning4j_tpu.perf.warmup import WarmupSpec  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_devtime():
    devtime.disable()
    devtime.reset_counters()
    yield
    devtime.disable()
    devtime.reset_counters()


def _probe_step():
    """Tiny scoped grad fn — the cheap capture donor."""
    def fwd(p, x):
        with devtime.scope("layer_0.DenseLayer"):
            h = jnp.tanh(x @ p["w0"])
        with devtime.scope("layer_1.OutputLayer"):
            o = h @ p["w1"]
        return jnp.sum(o ** 2)

    step = sentry.jit(jax.grad(fwd), name="devtime_probe")
    p = {"w0": jnp.ones((128, 128)), "w1": jnp.ones((128, 32))}
    x = jnp.ones((64, 128))
    step.warmup(p, x)
    return step, p, x


# -------------------------------------------------------------------------
# xplane wire parser
# -------------------------------------------------------------------------

def test_xplane_parser_reads_real_capture(tmp_path):
    step, p, x = _probe_step()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            jax.block_until_ready(step(p, x))
    paths = devtime.xplane_paths(str(tmp_path))
    assert paths and all(q.endswith(".xplane.pb") for q in paths)
    evs = []
    for q in paths:
        xs = devtime.read_xspace(q)
        assert xs["planes"], "no planes parsed"
        evs.extend(devtime.op_events(xs))
    assert evs, "no XLA-op execution events parsed"
    assert all(e["dur_ns"] > 0 for e in evs)
    # the executed module is identifiable (the scope-map join key)
    assert any("devtime_probe" in e["module"] or "jit_" in e["module"]
               for e in evs)


def test_xplane_paths_explicit_file_and_newest_session(tmp_path):
    step, p, x = _probe_step()
    import shutil
    import time as _time
    d1, d2 = tmp_path / "one", tmp_path / "two"
    with jax.profiler.trace(str(d1)):
        jax.block_until_ready(step(p, x))
    _time.sleep(0.05)
    with jax.profiler.trace(str(d2)):
        jax.block_until_ready(step(p, x))
    # merge target: every plane of the NEWEST session only
    newest = devtime.xplane_paths(str(tmp_path))
    assert all(str(d2) in q for q in newest)
    # a second host's plane in the same session dir is merged, not
    # dropped (the multi-host fix)
    session_dir = Path(newest[0]).parent
    shutil.copy(newest[0], session_dir / "host2.xplane.pb")
    merged = devtime.xplane_paths(str(tmp_path))
    assert len(merged) == len(newest) + 1
    # explicit file argument reads exactly that plane
    assert devtime.xplane_paths(newest[0]) == [newest[0]]


# -------------------------------------------------------------------------
# HLO scope map
# -------------------------------------------------------------------------

def test_hlo_scope_map_attributes_forward_and_backward():
    step, p, x = _probe_step()
    ex = devtime.sentry_executables(step)
    assert ex, "warmup must leave an AOT executable"
    sm = devtime.hlo_scope_map(ex[0].as_text())
    assert sm["module"]
    scopes = {i["scope"] for i in sm["ops"].values() if i["scope"]}
    assert {"layer_0.DenseLayer", "layer_1.OutputLayer"} <= scopes
    # backward ops (transpose(jvp(...))) attribute to their layer
    assert any(i["backward"] and i["scope"] == "layer_0.DenseLayer"
               for i in sm["ops"].values())
    # dot flops are the exact 2·M·N·K of at least the fwd matmuls:
    # 64x128 @ 128x128 and 64x128 @ 128x32
    dot_flops = sorted(i["flops"] for i in sm["ops"].values()
                      if i["kind"] == "dot")
    assert 2 * 64 * 128 * 128 in dot_flops
    assert 2 * 64 * 32 * 128 in dot_flops


def test_scope_trace_time_only():
    """The annotation must not change the computed values."""
    def plain(x):
        return jnp.tanh(x @ x).sum()

    def scoped(x):
        with devtime.scope("layer_9.Probe"):
            return jnp.tanh(x @ x).sum()

    x = jnp.linspace(-1, 1, 64 * 64).reshape(64, 64)
    a = jax.jit(plain)(x)
    b = jax.jit(scoped)(x)
    assert float(a) == float(b)


# -------------------------------------------------------------------------
# roofline math
# -------------------------------------------------------------------------

def test_roofline_math_units():
    # compute-bound: intensity 100 F/B vs ridge 10 F/B
    r = devtime.roofline(flops=1e12, bytes_=1e10, seconds=2.0,
                         peak_flops=1e12, peak_bytes_per_s=1e11)
    assert r["bound"] == "compute"
    assert r["achieved_tflops"] == pytest.approx(0.5)
    assert r["compute_utilization"] == pytest.approx(0.5)
    assert r["utilization"] == pytest.approx(0.5)
    # memory-bound: intensity 1 F/B under the same ridge
    r = devtime.roofline(flops=1e10, bytes_=1e10, seconds=0.05,
                         peak_flops=1e12, peak_bytes_per_s=1e11)
    assert r["bound"] == "memory"
    assert r["memory_utilization"] == pytest.approx(2.0)
    assert r["utilization"] == pytest.approx(2.0)
    # degenerate inputs never divide by zero
    r = devtime.roofline(1.0, 1.0, 0.0, 1e12, 1e11)
    assert r["bound"] == "unknown" and r["utilization"] == 0.0


def test_gap_report_schema_and_ranking():
    cap = {
        "scopes": {
            "layer_0.Dense": {
                "device_ms": 8.0, "share": 0.4, "ops": 10,
                "fusions": 2, "backward_ms": 4.0,
                "custom_call_ms": 0.0, "flops": 1e9, "bytes": 1e8,
                "kinds": {"dot": 4},
                "roofline": {"utilization": 0.1, "bound": "memory"}},
            "op:flash_kernel": {
                "device_ms": 6.0, "share": 0.3, "ops": 2,
                "fusions": 0, "backward_ms": 0.0,
                "custom_call_ms": 5.9, "flops": 1e9, "bytes": 1e8,
                "kinds": {"custom-call": 2},
                "roofline": {"utilization": 0.2, "bound": "compute"}},
            "layer_1.Output": {
                "device_ms": 4.0, "share": 0.2, "ops": 5,
                "fusions": 1, "backward_ms": 1.0,
                "custom_call_ms": 0.0, "flops": 1e9, "bytes": 1e8,
                "kinds": {"dot": 2},
                "roofline": {"utilization": 0.9, "bound": "compute"}},
            "op:noise": {
                "device_ms": 0.1, "share": 0.005, "ops": 1,
                "fusions": 0, "backward_ms": 0.0,
                "custom_call_ms": 0.0, "flops": 0.0, "bytes": 0.0,
                "kinds": {"copy": 1}},
        }}
    gaps = devtime.gap_report(cap, top=10)
    assert [tuple(g) for g in gaps] == [devtime.GAP_KEYS] * 4
    assert [g["share"] for g in gaps] == sorted(
        (g["share"] for g in gaps), reverse=True)
    by = {g["scope"]: g for g in gaps}
    # big share + low utilization -> candidate
    assert by["layer_0.Dense"]["pallas_candidate"] is True
    # already a custom call -> never re-flagged
    assert by["op:flash_kernel"]["pallas_candidate"] is False
    # near-roofline -> XLA already won, no candidate
    assert by["layer_1.Output"]["pallas_candidate"] is False
    # sub-threshold share -> no candidate (no cost info either)
    assert by["op:noise"]["pallas_candidate"] is False


# -------------------------------------------------------------------------
# capture pipeline + scope coverage (the acceptance fence)
# -------------------------------------------------------------------------

def _smoke_net():
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(upd.Adam(learning_rate=1e-3)).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    activation="relu"))
            .layer(SubsamplingLayer(kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.convolutional(8, 8, 1)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 8, 8, 1)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    return net, x, y


def test_smoke_fit_attribution_covers_every_layer_type():
    net, x, y = _smoke_net()
    net.warmup([WarmupSpec(features=(8, 8, 8, 1), labels=(8, 3))])
    net.fit(x, y)                   # settle off the window
    # off-path fence FIRST: the fits above ran zero profiler sessions
    assert devtime.profiler_sessions() == 0
    assert devtime.captures() == 0
    rep = devtime.capture(
        lambda: [net.fit(x, y) for _ in range(2)],
        executables=devtime.sentry_executables(net._train_step_fn))
    cap = rep["capture"]
    scopes = cap["scopes"]
    # EVERY layer of the net appears in the attribution, named
    # layer_<i>.<RegisteredType>, with real device time
    for i, layer in enumerate(net.layers):
        key = f"layer_{i}.{type(layer).__name__}"
        assert key in scopes, (key, sorted(scopes))
        assert scopes[key]["device_ms"] > 0
    # the backward half attributes too (transpose(jvp(scope)) ops)
    assert sum(scopes[f"layer_{i}.{type(l).__name__}"]["backward_ms"]
               for i, l in enumerate(net.layers)) > 0
    # the optimizer phase is named, and attribution accounts for a
    # solid majority of measured device time
    assert "optimizer.update" in scopes
    assert cap["scope_coverage"] > 0.5
    # per-scope roofline rides along wherever cost info exists
    assert any("roofline" in e for e in scopes.values())
    assert devtime.captures() == 1 and devtime.profiler_sessions() == 1


def test_capture_publishes_devtime_gauges():
    step, p, x = _probe_step()
    devtime.capture(lambda: jax.block_until_ready(step(p, x)),
                    executables=devtime.sentry_executables(step))
    fams = obs_metrics.parse_exposition(obs_metrics.exposition())
    shares = {dict(labels).get("scope"): v for (n, labels), v
              in fams.items() if n == "dl4j_tpu_devtime_scope_share"}
    assert shares, "no scope-share gauges published"
    assert abs(sum(shares.values()) - 1.0) < 0.05
    assert fams.get(("dl4j_tpu_devtime_captures_total", ()), 0) >= 1
    # a second capture REPLACES the scope labelsets (no stale labels)
    devtime.capture(lambda: jax.block_until_ready(step(p, x)),
                    executables=devtime.sentry_executables(step))
    fams2 = obs_metrics.parse_exposition(obs_metrics.exposition())
    shares2 = {dict(labels).get("scope") for (n, labels), v
               in fams2.items()
               if n == "dl4j_tpu_devtime_scope_share"}
    assert shares2 <= set(shares) | shares2  # sanity: parse worked
    assert abs(sum(
        v for (n, _l), v in fams2.items()
        if n == "dl4j_tpu_devtime_scope_share") - 1.0) < 0.05


def test_cadence_monitor_and_off_path_fence():
    net, x, y = _smoke_net()
    net.fit(x, y)                   # compile outside any window
    s0 = devtime.profiler_sessions()
    assert s0 == 0                  # env unset: zero sessions so far
    devtime.configure(every=2, steps=2)
    for _ in range(4):
        net.fit(x, y)
    devtime.disable()
    assert devtime.captures() >= 1
    assert devtime.profiler_sessions() >= 1
    rep = devtime.last_report()
    assert rep is not None and rep["gaps"]
    # monitor off again: further fits never touch the profiler
    c0, s1 = devtime.captures(), devtime.profiler_sessions()
    for _ in range(2):
        net.fit(x, y)
    assert (devtime.captures(), devtime.profiler_sessions()) == (c0,
                                                                 s1)


def test_measure_capture_overhead_restores_state():
    c0, s0 = devtime.captures(), devtime.profiler_sessions()
    out = devtime.measure_capture_overhead(step_seconds=0.05,
                                           iters=2000)
    assert out["off_path_cost_us"] < 50.0
    assert out["monitor_enabled"] is False
    assert (devtime.captures(), devtime.profiler_sessions()) == (c0,
                                                                 s0)


# -------------------------------------------------------------------------
# xprof_summary integration (satellite: explicit file + merge)
# -------------------------------------------------------------------------

def test_xprof_summary_reads_capture_dir_and_file(tmp_path):
    import shutil

    import xprof_summary

    step, p, x = _probe_step()
    d = tmp_path / "cap"
    devtime.capture(lambda: jax.block_until_ready(step(p, x)),
                    executables=devtime.sentry_executables(step),
                    keep_dir=str(d))
    out = xprof_summary.summarize(str(d), top=5)
    assert "op class" in out and "%" in out
    planes = devtime.xplane_paths(str(d))
    # explicit file: exactly one plane read
    single = xprof_summary.summarize(planes[0], top=5)
    assert "planes: 1 file(s)" in single
    # a second host's plane doubles the merged totals, proving the
    # dir path merges instead of dropping hosts
    shutil.copy(planes[0],
                Path(planes[0]).parent / "hostB.xplane.pb")
    merged = xprof_summary.summarize(str(d), top=5)
    assert f"planes: {len(planes) + 1} file(s)" in merged


# -------------------------------------------------------------------------
# the join on the two forms of a trace (ISSUE 41)
# -------------------------------------------------------------------------
# A TPU's trace, as JAX 0.9.0 writes it (PERF.md §3): an op event is
# named by its whole HLO instruction and carries timing stats only; its
# METADATA entry holds ``tf_op`` (the framework path, scopes and all),
# ``program_id`` and XLA's own counts; the "XLA Modules" line names a
# program ``jit_admit(<id>)``; a loop holds its body; the
# ``/host:metadata`` plane holds each program's ``HloProto``. The CPU's:
# events on host lines with ``hlo_op`` / ``hlo_module`` / ``program_id``
# stats of their own, nothing in their metadata.

def _vi(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """``(field_no, value)`` pairs: an int is a varint, bytes/str a
    length-delimited field (a nested message is its bytes)."""
    out = bytearray()
    for fno, v in fields:
        if isinstance(v, int):
            out += _vi(fno << 3) + _vi(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _vi(fno << 3 | 2) + _vi(len(v)) + v
    return bytes(out)


class _Plane:
    """One XPlane under construction: stat names and event metadata
    are entered as they are used."""

    def __init__(self, name):
        self.name, self.stats, self.meta, self.lines = name, {}, [], []

    def stat(self, name, value):
        sid = self.stats.setdefault(name, len(self.stats) + 1)
        if isinstance(value, int):
            return _msg((1, sid), (3, value))
        return _msg((1, sid), (6 if isinstance(value, bytes) else 5,
                               value))

    def event_meta(self, name, key=None, **stats):
        key = key or len(self.meta) + 1
        self.meta.append((key, _msg(
            (1, key), (2, name),
            *((5, self.stat(k, v)) for k, v in stats.items()))))
        return key

    def line(self, name, events):
        """``events``: ``(metadata id, start ns, duration ns, stats)``."""
        self.lines.append(_msg((2, name), *(
            (4, _msg((1, mid), (2, s * 1000), (3, d * 1000),
                     *((4, self.stat(k, v)) for k, v in st.items())))
            for mid, s, d, st in events)))

    def encode(self):
        return _msg(
            (2, self.name), *((3, ln) for ln in self.lines),
            *((4, _msg((1, k), (2, m))) for k, m in self.meta),
            *((5, _msg((1, sid), (2, _msg((1, sid), (2, name)))))
              for name, sid in self.stats.items()))


def _hlo_proto(module, comps):
    """``comps``: ``[(id, name, [(id, name, opcode, op_name, operands,
    callees)])]`` as an ``HloProto``."""
    return _msg((1, _msg((1, module), *(
        (3, _msg((1, cname), (5, cid), *(
            (2, _msg((1, name), (2, kind), (35, iid),
                     *(((7, _msg((2, op_name))),) if op_name else ()),
                     *((36, o) for o in operands),
                     *((38, c) for c in callees)))
            for iid, name, kind, op_name, operands, callees in instrs)))
        for cid, cname, instrs in comps))))


_STEP = "jit(step)/dl4j.paged_decode.block_0/"


def _tpu_form(tmp_path):
    """Two ``jit_admit`` buckets whose ``fusion.12`` collide, one
    ``jit_step`` with a ``while`` over two runs of its body, a
    ``slice-done`` in front of the matmul it feeds, and one event that
    lies in no program and names none."""
    dev = _Plane("/device:TPU:0")
    mod = {pid: dev.event_meta(f"{name}({pid})")
           for pid, name in ((111, "jit_admit"), (222, "jit_admit"),
                             (333, "jit_step"))}
    fus = "%fusion.12 = bf16[512,4096]{1,0:T(8,128)(2,1)} fusion(...)"
    ops = {
        "mixer": dev.event_meta(
            fus, program_id=111, hlo_category="convolution fusion",
            flops=4000, bytes_accessed=100,
            tf_op="jit(admit)/dl4j.prefill.block_0/"
                  "dl4j.prefill.block_0.mixer/dot_general:"),
        # 200 bytes accessed, 150 of them in HBM (memory space 1)
        "ffn": dev.event_meta(
            fus, program_id=222, hlo_category="convolution fusion",
            flops=8000, bytes_accessed=200,
            memory_access_breakdown=_msg(
                (1, _msg((1, 1), (2, 1), (3, 150))),
                (1, _msg((1, 1), (2, 3), (3, 50)))),
            tf_op="jit(admit)/dl4j.prefill.block_0/"
                  "dl4j.prefill.block_0.ffn/dot_general:"),
        "while": dev.event_meta(
            "%while.1 = (s32[], bf16[64,7168]) while(...), body=%body",
            program_id=333, hlo_category="while"),
        "body": dev.event_meta(
            "%fusion.7 = bf16[16,2048]{1,0} fusion(...), kind=kOutput",
            program_id=333, hlo_category="convolution fusion",
            tf_op=_STEP + "dl4j.ops.moe_experts/while/body/"
                  "dot_general:"),
        "done": dev.event_meta(
            "%slice-done.1 = bf16[1024,14336] async-done(%slice-start.1)",
            program_id=333, hlo_category="async-done"),
        "lost": dev.event_meta("%copy.3 = bf16[8]{0} copy(%p.1)"),
    }
    dev.line("XLA Modules", [(mod[111], 1000, 200, {}),
                             (mod[222], 2000, 400, {}),
                             (mod[333], 3000, 1500, {})])
    dev.line("XLA Ops", [
        (ops["mixer"], 1050, 100, {}), (ops["ffn"], 2050, 300, {}),
        (ops["while"], 3100, 1000, {}), (ops["body"], 3150, 400, {}),
        (ops["body"], 3600, 400, {}), (ops["done"], 4200, 250, {}),
        (ops["lost"], 9000, 50, {})])
    host = _Plane("/host:metadata")
    host.event_meta("jit_step(333)", key=333, **{"Hlo Proto": _hlo_proto(
        "jit_step", [
            (2, "body", [(5, "fusion.7", "fusion",
                          _STEP + "dl4j.ops.moe_experts/dot_general",
                          [], [])]),
            (1, "main", [
                (1, "slice-start.1", "async-start", "", [], []),
                (2, "slice-done.1", "async-done", "", [1], []),
                (3, "fusion.9", "fusion", _STEP + "dl4j.paged_decode"
                 ".block_0.ffn/dot_general", [2], []),
                (4, "while.1", "while", "", [3], [2])])])})
    path = tmp_path / "tpu.xplane.pb"
    path.write_bytes(_msg((1, dev.encode()), (1, host.encode())))
    return str(path), None


_CPU_HLO = """HloModule jit_f, entry_computation_layout={()->f32[8]{0}}

ENTRY %main.3 () -> f32[8] {
  %dot.1 = f32[8]{0} dot(f32[8,8]{1,0} %p.0, f32[8]{0} %p.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_type="dot_general" op_name="jit(f)/dl4j.layer_0.DenseLayer/dot_general"}
  ROOT %copy.2 = f32[8]{0} copy(f32[8]{0} %dot.1)
}
"""


def _cpu_form(tmp_path):
    """The thunk executor's events, joined through a map made of the
    executable's text. ``jit_g`` has no map (a name that only STARTS
    like a mapped one is another program's), and a ``tf_op`` stat on
    the EVENT is not where the pinned JAX writes a framework path."""
    cpu = _Plane("/host:CPU")
    names = {n: cpu.event_meta(n) for n in ("dot.1", "copy.2", "while.5")}
    cpu.line("tf_XLAPjRtCpuClient/1", [
        (names["dot.1"], 100, 700,
         {"hlo_op": "dot.1", "hlo_module": "jit_f", "program_id": 5}),
        (names["copy.2"], 900, 100,
         {"hlo_op": "copy.2", "hlo_module": "jit_f", "program_id": 5}),
        (names["while.5"], 2000, 500,
         {"hlo_op": "while.5", "hlo_module": "jit_f_other",
          "program_id": 6}),
        (names["dot.1"], 2100, 300,
         {"hlo_op": "dot.1", "hlo_module": "jit_f_other",
          "program_id": 6, "tf_op": "jit(g)/dl4j.layer_9.Probe/dot:"})])
    path = tmp_path / "cpu.xplane.pb"
    path.write_bytes(_msg((1, cpu.encode())))
    return str(path), {"jit_f": devtime.hlo_scope_map(_CPU_HLO)}


#: form -> scope -> device_ms, every nanosecond of the fixture
_JOINED = {
    "tpu": {"prefill.block_0.mixer": 100e-6,      # program 111's fusion.12
            "prefill.block_0.ffn": 300e-6,        # program 222's fusion.12
            # the body's two runs, and the loop's own 200 ns by its body
            "ops.moe_experts": 1000e-6,
            # a wait for a weight's slice is time of the matmul behind it
            "paged_decode.block_0.ffn": 250e-6,
            devtime.UNJOINED: 50e-6},
    # jit_f's dot by the map, its copy unscoped; jit_f_other has no
    # map, and its while is a container on a host line: its children
    # report their own time
    "cpu": {"layer_0.DenseLayer": 700e-6, "op:copy": 100e-6,
            "op:dot": 300e-6},
}


@pytest.mark.parametrize("form", ["tpu", "cpu"])
def test_join_lands_each_op_in_its_own_programs_scope(form, tmp_path):
    path, maps = (_tpu_form if form == "tpu" else _cpu_form)(tmp_path)
    att = devtime.attribute([path], maps=maps, peaks=(1e12, 1e11))
    got = {k: v["device_ms"] for k, v in att["scopes"].items()}
    want = _JOINED[form]
    assert got == pytest.approx(want)
    assert att["total_device_ms"] == pytest.approx(sum(want.values()))
    events = devtime.joined_events([path], maps)
    by_op = {(e["program_id"], e["op"]): e for e in events}
    if form == "tpu":
        assert by_op[(111, "fusion.12")]["path"] == (
            "prefill.block_0", "prefill.block_0.mixer")
        assert by_op[(222, "fusion.12")]["scope"] == "prefill.block_0.ffn"
        assert by_op[(333, "while.1")]["self_ns"] == pytest.approx(200)
        assert by_op[(333, "while.1")]["via"] == "body"
        assert by_op[(333, "slice-done.1")]["via"] == "consumer"
        assert by_op[(0, "copy.3")]["module"] == ""
        assert by_op[(0, "copy.3")]["scope"] == devtime.UNJOINED
        # XLA's own counts ride along, and a program is counted by its
        # events on the Modules line, two names apart
        assert att["scopes"]["prefill.block_0.ffn"]["flops"] == 8000
        assert att["scopes"]["prefill.block_0.ffn"]["bytes"] == 150
        assert att["scopes"]["prefill.block_0.mixer"]["bytes"] == 100
        assert {k: v["executions"] for k, v in att["modules"].items()} \
            == {"jit_admit(111)": 1, "jit_admit(222)": 1, "jit_step": 1}
        assert att["scope_coverage"] == pytest.approx(1650 / 1700)
    else:
        assert by_op[(5, "dot.1")]["via"] == "own"
        assert by_op[(6, "dot.1")]["scope"] is None
        assert att["modules"]["jit_f"]["executions"] == 1


def test_a_map_is_never_taken_by_name_where_two_programs_share_it(
        tmp_path):
    """Two executables under one module name: neither's map may speak
    for an event that names only the module."""
    cpu = _Plane("/host:CPU")
    mid = cpu.event_meta("dot.1")
    cpu.line("tf_XLAPjRtCpuClient/1", [
        (mid, 100, 700, {"hlo_op": "dot.1", "hlo_module": "jit_f",
                         "program_id": 5})])
    path = tmp_path / "two.xplane.pb"
    path.write_bytes(_msg((1, cpu.encode())))

    class Text:
        def __init__(self, text):
            self.text = text

        def as_text(self):
            return self.text

    one = devtime.executable_maps([Text(_CPU_HLO)])
    two = devtime.executable_maps([Text(_CPU_HLO), Text(
        _CPU_HLO.replace("layer_0.DenseLayer", "layer_7.Other"))])
    assert one["jit_f"]["programs"] == 1 and two["jit_f"]["programs"] == 2
    scope = {n: devtime.joined_events([str(path)], m)[0]["scope"]
             for n, m in (("one", one), ("two", two))}
    assert scope == {"one": "layer_0.DenseLayer", "two": None}

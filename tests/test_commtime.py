"""Communication observatory (obs/commtime.py — ARCHITECTURE.md §19).

Fences: the per-device ring wire model is exact, replica-group/pair
parsing handles the literal and iota HLO forms, the collective walker
joins ``dl4j.*`` scopes through the scope map and never double-counts
async ``-done`` halves, the static wire ledger reproduces the PR 5
byte model on the ZeRO sharded step (reduce-scatter ≈ grad/N shard
under ``zero.reduce_scatter``, all-gather ≈ param bytes under
``zero.all_gather``) across DP / ZeRO / ZeRO-overlap / DP×TP / SP /
EP, the comm-view roofline math is exact, a collective-dominated
scope flips ``gap_report``'s bound axis to ``"wire"`` and is never a
Pallas candidate, the capture pipeline publishes the
``dl4j_tpu_comm_*`` gauges, and — the PR 2 contract —
``DL4J_TPU_COMMTIME`` unset means zero profiler sessions and zero
captures through the fit loops (counter-asserted).
"""
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from deeplearning4j_tpu.nn import (MultiLayerNetwork,  # noqa: E402
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.config import InputType  # noqa: E402
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer,  # noqa: E402
                                          DenseLayer, OutputLayer,
                                          SubsamplingLayer)
from deeplearning4j_tpu.nn import updaters as upd  # noqa: E402
from deeplearning4j_tpu.obs import commtime, devtime  # noqa: E402
from deeplearning4j_tpu.obs import metrics as obs_metrics  # noqa: E402
from deeplearning4j_tpu.parallel import ParallelWrapper  # noqa: E402
from deeplearning4j_tpu.parallel.mesh import make_mesh  # noqa: E402

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="wire-ledger gates pin an 8-device mesh "
           "(--xla_force_host_platform_device_count=8)")


@pytest.fixture(autouse=True)
def _clean_commtime():
    commtime.disable()
    commtime.reset_counters()
    yield
    commtime.disable()
    commtime.reset_counters()


def _param_bytes(tree):
    return sum(int(np.prod(p.shape)) * p.dtype.itemsize
               for p in jax.tree_util.tree_leaves(tree))


def _mlp_wrapper(sharded_update=True, gather_overlap=False):
    """Tiny ZeRO-able DP MLP on the 8-device mesh — the ledger-gate
    donor (same geometry as the probe the assertion bands were pinned
    against: params 32·64+64 + 64·16+16 = 3152 f32)."""
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(upd.Adam(learning_rate=1e-3)).list()
            .layer(DenseLayer(n_out=64, activation="relu"))
            .layer(OutputLayer(n_out=16, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(32)).build())
    net = MultiLayerNetwork(conf).init()
    w = ParallelWrapper(net, workers=8, sharded_update=sharded_update,
                        gather_overlap=gather_overlap)
    w._prepare()
    dshard = NamedSharding(w.mesh, P("data"))
    x = jax.device_put(jnp.zeros((64, 32), jnp.float32), dshard)
    y = jax.device_put(jnp.zeros((64, 16), jnp.float32), dshard)
    rng = jax.random.PRNGKey(0)
    if gather_overlap:
        args = (w._pshard, w._dp_state, net.state, x, y, rng)
    elif sharded_update:
        args = (net.params, w._dp_state, net.state, x, y, rng)
    else:
        args = (net.params, net.opt_state, net.state, x, y, rng)
    return net, w, args


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


# -------------------------------------------------------------------------
# ring wire model + HLO attribute parsing
# -------------------------------------------------------------------------

def test_ring_wire_bytes_model():
    # all-reduce = reduce-scatter + all-gather over the ring
    assert commtime.ring_wire_bytes("all-reduce", 1024, 8) \
        == 2 * 1024 * 7 / 8
    # all-gather result is the FULL tensor; each device sends a shard
    assert commtime.ring_wire_bytes("all-gather", 800, 8) == 700.0
    # reduce-scatter result is the SHARD
    assert commtime.ring_wire_bytes("reduce-scatter", 128, 8) \
        == 128 * 7
    assert commtime.ring_wire_bytes("collective-permute", 4096, 8) \
        == 4096.0
    assert commtime.ring_wire_bytes("all-to-all", 800, 8) \
        == 800 * 7 / 8
    # a two-device all-reduce ring moves exactly the tensor bytes
    assert commtime.ring_wire_bytes("all-reduce", 2048, 2) == 2048.0
    # one-device groups move nothing
    for k in ("all-reduce", "all-gather", "reduce-scatter",
              "collective-permute", "all-to-all"):
        assert commtime.ring_wire_bytes(k, 1e9, 1) == 0.0


def test_parse_replica_groups_literal_iota_and_absent():
    lit = commtime.parse_replica_groups(
        "f32[8]{0} all-reduce(%g), replica_groups={{0,1,2,3},{4,5,6,7}},"
        " to_apply=%add")
    assert lit == frozenset({frozenset({0, 1, 2, 3}),
                             frozenset({4, 5, 6, 7})})
    # iota form with a transpose: [4,2]<=[2,4]T(1,0) strides the axis
    iota = commtime.parse_replica_groups(
        "replica_groups=[4,2]<=[2,4]T(1,0)")
    assert iota == frozenset({frozenset({0, 4}), frozenset({1, 5}),
                              frozenset({2, 6}), frozenset({3, 7})})
    plain = commtime.parse_replica_groups("replica_groups=[2,4]<=[8]")
    assert plain == frozenset({frozenset({0, 1, 2, 3}),
                               frozenset({4, 5, 6, 7})})
    # absent/empty groups: None (one group of every device)
    assert commtime.parse_replica_groups(
        "all-reduce(%g), to_apply=%add") is None


def test_parse_source_target_pairs():
    pairs = commtime.parse_source_target_pairs(
        "collective-permute(%kv), "
        "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}")
    assert pairs == [(0, 1), (1, 2), (2, 3), (3, 0)]
    assert commtime.parse_source_target_pairs(
        "all-reduce(%g), to_apply=%add") is None


# -------------------------------------------------------------------------
# the collective walker on synthetic HLO (scope join, async halves,
# while-body trips, group-sized rings)
# -------------------------------------------------------------------------

_SYNTH_HLO = """\
HloModule synth_step, entry_computation_layout={(f32[256]{0})->f32[256]{0}}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

ENTRY %main (p0: f32[256]) -> f32[256] {
  %p0 = f32[256]{0} parameter(0)
  %all-reduce.1 = f32[256]{0} all-reduce(%p0), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add, metadata={op_name="jit(step)/jit(main)/dl4j.zero.grad_sync/psum"}
  %all-gather-start.1 = f32[2048]{0} all-gather-start(%all-reduce.1), replica_groups=[1,8]<=[8], dimensions={0}, metadata={op_name="jit(step)/dl4j.zero.all_gather/all_gather"}
  %all-gather-done.1 = f32[2048]{0} all-gather-done(%all-gather-start.1)
  %collective-permute.1 = f32[256]{0} collective-permute(%p0), source_target_pairs={{0,1},{1,2},{2,3},{3,0},{4,5},{5,6},{6,7},{7,4}}, metadata={op_name="jit(step)/while/body/dl4j.sp.ring_attention/ppermute"}
  ROOT %anon = f32[256]{0} all-reduce(%collective-permute.1), to_apply=%add
}
"""


def test_collective_records_synthetic_hlo():
    recs = commtime.collective_records(_SYNTH_HLO, n_devices=8)
    assert [r["kind"] for r in recs] == [
        "all-reduce", "all-gather", "collective-permute", "all-reduce"]
    ar, ag, cp, anon = recs
    assert ar["module"] == "synth_step"
    assert ar["scope"] == "zero.grad_sync"
    assert ar["tensor_bytes"] == 256 * 4
    # ring sized by the PARSED groups: two 4-rings, not the 8 mesh
    assert ar["group_size"] == 4
    assert ar["replica_groups"] == frozenset(
        {frozenset({0, 1, 2, 3}), frozenset({4, 5, 6, 7})})
    assert ar["wire_bytes"] == pytest.approx(2 * 1024 * 3 / 4)
    # the async -start half IS the op; the -done half never counts
    assert ag["op"] == "all-gather-start.1"
    assert ag["scope"] == "zero.all_gather"
    assert ag["tensor_bytes"] == 2048 * 4
    assert ag["group_size"] == 8
    assert ag["wire_bytes"] == pytest.approx(2048 * 4 / 8 * 7)
    assert not any(r["op"].startswith("all-gather-done") for r in recs)
    # while-body permute: one neighbor hop per ring trip
    assert cp["scope"] == "sp.ring_attention"
    assert cp["in_while"] is True and cp["trips"] == 8
    assert cp["source_target_pairs"][:2] == [(0, 1), (1, 2)]
    assert cp["wire_bytes"] == pytest.approx(1024 * 8)
    # no groups + no scope: n_devices ring, anonymous record
    assert anon["scope"] is None
    assert anon["group_size"] == 8 and anon["trips"] == 1
    assert anon["backward"] is False


def test_collective_records_uniform_ring_override():
    # the legacy collective_volume knob: every ring sized to the mesh
    recs = commtime.collective_records(_SYNTH_HLO, uniform_ring=8)
    assert recs[0]["group_size"] == 8
    assert recs[0]["wire_bytes"] == pytest.approx(2 * 1024 * 7 / 8)


class _FakeCompiled:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_wire_ledger_aggregates_scopes_and_kinds():
    led = commtime.wire_ledger([_FakeCompiled(_SYNTH_HLO), None],
                               n_devices=8)
    assert led["programs"] == 1          # None executables filtered
    assert led["n_devices"] == 8
    assert set(led["by_scope"]) == {"zero.grad_sync",
                                    "zero.all_gather",
                                    "sp.ring_attention",
                                    "op:all-reduce"}
    assert led["by_kind"]["all-reduce"]["count"] == 2
    assert led["by_kind"]["all-gather"]["count"] == 1
    assert led["wire_bytes"] == pytest.approx(
        sum(r["wire_bytes"] for r in led["records"]))
    # tensor-byte rollup multiplies the while-body trip count
    assert led["by_scope"]["sp.ring_attention"]["tensor_bytes"] \
        == pytest.approx(1024 * 8)
    assert led["by_scope"]["zero.grad_sync"]["kinds"] \
        == {"all-reduce": 1}


# -------------------------------------------------------------------------
# compiled programs: every parallelism mode's ledger
# -------------------------------------------------------------------------

@needs_mesh
def test_dp_dense_wrapper_allreduce_wire():
    net, w, args = _mlp_wrapper(sharded_update=False)
    compiled = w._step.lower(*args).compile()
    led = commtime.wire_ledger([compiled], n_devices=8)
    # dense DP syncs grads with all-reduce ONLY — a reduce-scatter
    # here would mean the replicated baseline silently went ZeRO
    assert set(led["by_kind"]) == {"all-reduce"}
    want = 2 * _param_bytes(net.params) * 7 / 8
    assert want * 0.98 < led["wire_bytes"] < want * 1.06


@needs_mesh
def test_zero_ledger_scope_attribution_matches_byte_model():
    net, w, args = _mlp_wrapper(sharded_update=True)
    compiled = w._step.lower(*args).compile()
    led = commtime.wire_ledger([compiled], n_devices=8)
    by = led["by_scope"]
    p = _param_bytes(net.params)
    # PR 5 byte model through the scope join: reduce-scatter results
    # ≈ grad/8 shards, all-gather results ≈ full params — both ride
    # the same (N/n)·(n−1) ring wire
    shard_wire = p / 8 * 7
    rs, ag = by["zero.reduce_scatter"], by["zero.all_gather"]
    assert shard_wire * 0.95 < rs["wire_bytes"] < shard_wire * 1.2
    assert shard_wire * 0.95 < ag["wire_bytes"] < shard_wire * 1.2
    assert p / 8 * 0.95 < rs["tensor_bytes"] < p / 8 * 1.2
    assert p * 0.95 < ag["tensor_bytes"] < p * 1.2
    assert set(rs["kinds"]) == {"reduce-scatter"}
    assert set(ag["kinds"]) == {"all-gather"}
    # the loss pmean is the only anonymous collective left (the
    # in-repo emitters are scoped — lint rule 11's fence)
    assert [k for k in by if k.startswith("op:")] == ["op:all-reduce"]


@needs_mesh
def test_zero_gather_overlap_keeps_scope_attribution():
    net, w, args = _mlp_wrapper(sharded_update=True,
                                gather_overlap=True)
    compiled = w._step.lower(*args).compile()
    led = commtime.wire_ledger([compiled], n_devices=8)
    by = led["by_scope"]
    # the overlap step carries flat 1/N shards and gathers params up
    # front — same scopes, same byte model as the non-overlap path
    p = _param_bytes(net.params)
    shard_wire = p / 8 * 7
    assert shard_wire * 0.9 < by["zero.all_gather"]["wire_bytes"] \
        < shard_wire * 1.3
    assert shard_wire * 0.9 < by["zero.reduce_scatter"]["wire_bytes"] \
        < shard_wire * 1.3
    assert led["by_kind"]["all-gather"]["count"] >= 1
    assert led["by_kind"]["reduce-scatter"]["count"] >= 1


@needs_mesh
def test_dp_tp_rings_sized_per_parsed_group():
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2),
                ("data", "tensor"))
    d, h = 64, 256
    params = {"W1": jnp.zeros((d, h), jnp.float32),
              "W2": jnp.zeros((h, d), jnp.float32)}
    shard = {"W1": NamedSharding(mesh, P(None, "tensor")),
             "W2": NamedSharding(mesh, P("tensor", None))}
    x = jnp.zeros((32, d), jnp.float32)

    def fwd(p, x):
        hdn = jax.nn.relu(x @ p["W1"])
        return jnp.sum((hdn @ p["W2"]) ** 2)

    step = jax.jit(lambda p, x: jax.value_and_grad(fwd)(p, x),
                   in_shardings=(shard, NamedSharding(mesh,
                                                      P("data"))))
    compiled = step.lower(jax.device_put(params, shard), x).compile()
    recs = commtime.collective_records(compiled.as_text())
    assert recs and all(r["kind"] == "all-reduce" for r in recs)
    # tensor-axis activation psum rings over 2, data-axis grad sync
    # over 4 — NEVER a flat 8-ring on this 4×2 mesh
    sizes = sorted({r["group_size"] for r in recs})
    assert sizes == [2, 4]
    for r in recs:
        assert r["wire_bytes"] == pytest.approx(
            commtime.ring_wire_bytes("all-reduce", r["tensor_bytes"],
                                     r["group_size"]))
    # the 2-ring moves exactly the activation-grad tensor bytes
    two = [r for r in recs if r["group_size"] == 2]
    assert two and all(r["wire_bytes"] == pytest.approx(
        r["tensor_bytes"]) for r in two)


@needs_mesh
def test_ep_moe_rings_span_expert_axis():
    from deeplearning4j_tpu.parallel.moe import MixtureOfExperts
    mesh = make_mesh({"expert": 8})
    moe = MixtureOfExperts(d_model=8, d_hidden=16, num_experts=8,
                           top_k=2)
    params = moe.shard(moe.init(), mesh, axis="expert")
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 8))

    @jax.jit
    def step(p, x):
        def loss(p):
            out, aux = moe.apply(p, x)
            return jnp.mean(jnp.square(out)) + 0.01 * aux
        return jax.value_and_grad(loss)(p)

    compiled = step.lower(params, x).compile()
    recs = commtime.collective_records(compiled.as_text())
    assert recs
    for r in recs:
        # GSPMD lowers the expert mixing to all-reduce over the FULL
        # expert axis; each record's wire obeys the ring model
        assert r["kind"] == "all-reduce" and r["group_size"] == 8
        assert r["wire_bytes"] == pytest.approx(
            2 * r["tensor_bytes"] * 7 / 8)
    led = commtime.wire_ledger([compiled], n_devices=8)
    assert led["wire_bytes"] == pytest.approx(
        sum(r["wire_bytes"] for r in recs))


@needs_mesh
def test_sp_ring_attention_permute_trips():
    from deeplearning4j_tpu.parallel.ring_attention import \
        ring_self_attention
    mesh = make_mesh({"seq": 8})
    q = jnp.zeros((1, 1024, 4, 32), jnp.bfloat16)

    def loss(q):
        return jnp.sum(
            ring_self_attention(q, q, q, mesh, causal=True)
            .astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.value_and_grad(loss)).lower(q).compile()
    recs = commtime.collective_records(compiled.as_text())
    perms = [r for r in recs if r["kind"] == "collective-permute"]
    assert perms, "ring attention emitted no collective-permute"
    # the fori_loop KV rotation pays one hop per ring trip
    looped = [r for r in perms if r["in_while"]]
    assert looped
    for r in looped:
        assert r["trips"] == r["group_size"]
        assert r["wire_bytes"] == pytest.approx(
            r["tensor_bytes"] * r["trips"])


# -------------------------------------------------------------------------
# comm-view roofline math + the gap report's wire axis
# -------------------------------------------------------------------------

def test_comm_view_roofline_math():
    att = {
        "total_device_ms": 10.0, "device_steps": 2, "planes": 1,
        "modules": {},
        "scopes": {
            "zero.reduce_scatter": {
                "device_ms": 6.0, "comm_ms": 4.0,
                "kinds": {"reduce-scatter-start": 2,
                          "reduce-scatter-done": 2}},
            "layer_0.Dense": {
                "device_ms": 4.0, "comm_ms": 0.0,
                "kinds": {"dot": 3}},
        }}
    ledger = {
        "wire_bytes": 22064.0,
        "by_scope": {
            "zero.reduce_scatter": {"wire_bytes": 11032.0,
                                    "tensor_bytes": 1576.0,
                                    "kinds": {"reduce-scatter": 2}},
            "ghost.ledger_only": {"wire_bytes": 1.0,
                                  "tensor_bytes": 1.0, "kinds": {}},
        }}
    view = commtime.comm_view(att, ledger=ledger, peak_ici=100e9)
    # a scope with no collective time, no collective kinds, and no
    # ledger row is dropped; a ledger row with no runtime scope never
    # invents device time
    assert set(view["scopes"]) == {"zero.reduce_scatter"}
    r = view["scopes"]["zero.reduce_scatter"]
    assert r["collective_ms"] == 4.0
    assert r["share"] == pytest.approx(0.4)
    # async halves roll up to ONE base kind (the -done half dropped)
    assert r["kinds"] == {"reduce-scatter": 2}
    assert r["wire_bound"] is True       # 4.0 > 0.5 · 6.0
    assert r["wire_bytes_per_step"] == 11032.0
    # achieved GB/s = wire/step · steps / collective seconds
    want_gbs = 11032.0 * 2 / (4.0 / 1e3) / 1e9
    assert r["achieved_gbs"] == pytest.approx(want_gbs, rel=1e-3)
    # published value is rounded to 6 decimals
    assert r["link_utilization"] == pytest.approx(
        want_gbs * 1e9 / 100e9, abs=1e-6)
    assert view["collective_ms"] == pytest.approx(4.0)
    assert view["comm_share"] == pytest.approx(0.4)
    assert view["by_kind"] == {"reduce-scatter": 2}
    assert view["wire_bound_scopes"] == ["zero.reduce_scatter"]
    assert view["peak_ici_gbs"] == pytest.approx(100.0)
    assert view["wire_bytes_per_step"] == 22064.0
    # XLA:CPU captures time host thunks, not ICI — flagged as such
    assert view["estimate_only"] is True


def test_comm_view_steps_fall_back_to_module_executions():
    att = {"total_device_ms": 1.0, "device_steps": 0, "planes": 1,
           "modules": {"jit_step": {"executions": 5}},
           "scopes": {"s": {"device_ms": 1.0, "comm_ms": 1.0,
                            "kinds": {"all-reduce": 1}}}}
    ledger = {"wire_bytes": 100.0,
              "by_scope": {"s": {"wire_bytes": 100.0,
                                 "tensor_bytes": 50.0, "kinds": {}}}}
    view = commtime.comm_view(att, ledger=ledger, peak_ici=1e9)
    # 100 B/step · 5 executions / 1 ms
    assert view["scopes"]["s"]["achieved_gbs"] == pytest.approx(
        100.0 * 5 / (1.0 / 1e3) / 1e9, rel=1e-3)


def test_gap_report_wire_bound_axis():
    cap = {"scopes": {
        "zero.all_gather": {
            "device_ms": 8.0, "share": 0.5, "ops": 4, "fusions": 0,
            "backward_ms": 0.0, "comm_ms": 6.0, "custom_call_ms": 0.0,
            "flops": 1e9, "bytes": 1e8, "kinds": {"all-gather": 4},
            "roofline": {"utilization": 0.05, "bound": "memory"}},
        "layer_0.Dense": {
            "device_ms": 8.0, "share": 0.5, "ops": 4, "fusions": 1,
            "backward_ms": 2.0, "comm_ms": 0.5, "custom_call_ms": 0.0,
            "flops": 1e9, "bytes": 1e8, "kinds": {"dot": 2},
            "roofline": {"utilization": 0.05, "bound": "memory"}},
    }}
    gaps = devtime.gap_report(cap, top=10)
    assert [tuple(g) for g in gaps] == [devtime.GAP_KEYS] * 2
    by = {g["scope"]: g for g in gaps}
    # collective-dominated: the interconnect is the ceiling — bound
    # flips to "wire" and no kernel can close it
    assert by["zero.all_gather"]["bound"] == "wire"
    assert by["zero.all_gather"]["comm_ms"] == 6.0
    assert by["zero.all_gather"]["pallas_candidate"] is False
    # the compute twin below the roofline stays a candidate
    assert by["layer_0.Dense"]["bound"] == "memory"
    assert by["layer_0.Dense"]["pallas_candidate"] is True


# -------------------------------------------------------------------------
# capture pipeline + metric surface + the off-path fence
# -------------------------------------------------------------------------

def _threaded_runner(compiled, args):
    """One-step runner that threads the carried state through — the
    step donates argnums (0, 1, 2), so re-calling with the original
    arrays would hit deleted buffers."""
    carried = list(args[:3])
    rest = args[3:]

    def run_once():
        p, s, st, loss = compiled(carried[0], carried[1], carried[2],
                                  *rest)
        carried[0], carried[1], carried[2] = p, s, st
        jax.block_until_ready(loss)

    return run_once


@needs_mesh
def test_capture_attributes_and_publishes_zero_scopes():
    net, w, args = _mlp_wrapper(sharded_update=True)
    compiled = w._step.lower(*args).compile()
    run_once = _threaded_runner(compiled, args)
    run_once()                       # settle OUTSIDE any window
    assert commtime.captures() == 0
    assert commtime.profiler_sessions() == 0

    rep = commtime.capture(run_once, executables=[compiled])
    assert commtime.captures() == 1
    assert commtime.profiler_sessions() == 1
    assert rep["label"] == "on_demand" and rep["capture_wall_s"] > 0
    assert commtime.last_report() is rep
    view = rep["comm"]
    assert view["collective_ms"] > 0
    assert view["estimate_only"] is True         # CPU capture
    assert {"reduce-scatter", "all-gather"} <= set(view["by_kind"])
    sc = view["scopes"]
    assert "zero.reduce_scatter" in sc and "zero.all_gather" in sc
    p = _param_bytes(net.params)
    rs = sc["zero.reduce_scatter"]
    assert rs["collective_ms"] > 0
    assert p / 8 * 7 * 0.95 < rs["wire_bytes_per_step"] \
        < p / 8 * 7 * 1.2
    assert "achieved_gbs" in rs and "link_utilization" in rs
    assert rep["ledger"]["programs"] == 1

    # the standing-registry surface: scrape shows THIS capture
    fams = obs_metrics.parse_exposition(obs_metrics.exposition())
    assert fams[("dl4j_tpu_comm_captures_total", ())] >= 1.0
    wire_scopes = {dict(labels)["scope"]
                   for (name, labels) in fams
                   if name == "dl4j_tpu_comm_scope_wire_bytes_per_step"}
    assert {"zero.reduce_scatter", "zero.all_gather"} <= wire_scopes
    op_kinds = {dict(labels)["kind"]
                for (name, labels) in fams
                if name == "dl4j_tpu_comm_op_count"}
    assert {"reduce-scatter", "all-gather"} <= op_kinds
    share = {dict(labels)["scope"]: v for (name, labels), v
             in fams.items()
             if name == "dl4j_tpu_comm_scope_step_share"}
    assert 0.0 < share["zero.reduce_scatter"] <= 1.0


@needs_mesh
def test_xprof_summary_comm_mode(tmp_path):
    net, w, args = _mlp_wrapper(sharded_update=True)
    compiled = w._step.lower(*args).compile()
    run_once = _threaded_runner(compiled, args)
    run_once()
    commtime.capture(run_once, executables=[compiled],
                     keep_dir=str(tmp_path))
    spec = importlib.util.spec_from_file_location(
        "xprof_summary", REPO / "tools" / "xprof_summary.py")
    xp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(xp)
    out = xp.summarize_comm(str(tmp_path))
    # offline twin of tpu_watch --comm: per-scope collective table
    # from the kept xplane session. No executable is handed over: the
    # join reads each program's HLO from the trace's own
    # ``/host:metadata`` plane, by program id, so the dl4j.* scopes
    # appear here as they do on a TPU capture
    assert "collective" in out
    assert "zero.reduce_scatter" in out and "zero.all_gather" in out
    assert "| scope | collective ms |" in out
    assert "estimate-only" in out        # non-TPU capture is flagged
    assert "wire-bound scopes:" in out


def test_off_path_fence_counters_zero(monkeypatch):
    monkeypatch.delenv("DL4J_TPU_COMMTIME", raising=False)
    net, x, y = _smoke_net()
    for _ in range(3):
        net.fit(x, y)
    # the PR 2 bar: env unset — the fit-loop hooks are one
    # module-global branch, zero profiler sessions, zero captures
    assert commtime.captures() == 0
    assert commtime.profiler_sessions() == 0
    ov = commtime.measure_capture_overhead(step_seconds=0.01,
                                           iters=20000)
    assert ov["monitor_enabled"] is False
    assert ov["off_path_cost_us"] < 50.0
    assert ov["off_path_pct_of_step"] < 1.0
    # the probe restored the counters it touched
    assert commtime.captures() == 0
    assert commtime.profiler_sessions() == 0


def test_cadence_monitor_and_refence():
    net, x, y = _smoke_net()
    net.fit(x, y)                    # compile outside any window
    assert commtime.profiler_sessions() == 0
    commtime.configure(every=2, steps=2)
    for _ in range(4):
        net.fit(x, y)
    commtime.disable()
    assert commtime.captures() >= 1
    assert commtime.profiler_sessions() >= 1
    rep = commtime.last_report()
    assert rep is not None and rep["label"] == "cadence"
    assert rep["comm"]["total_device_ms"] > 0
    # monitor off again: further fits never touch the profiler
    n = commtime.captures()
    s = commtime.profiler_sessions()
    for _ in range(2):
        net.fit(x, y)
    assert commtime.captures() == n
    assert commtime.profiler_sessions() == s


@needs_mesh
def test_comm_report_gates_byte_model():
    rep = commtime.comm_report(n_devices=8, hidden=32, features=16,
                               classes=4)
    assert not rep.get("skipped"), rep
    gates = rep["gates"]
    # the bench.py "comm" section's acceptance: reduce-scatter tensor
    # bytes ≈ grad/8 shard, all-gather tensor bytes ≈ full params
    assert gates["reduce_scatter_tensor_over_grad_shard"] \
        == pytest.approx(1.0, rel=0.2)
    assert gates["all_gather_tensor_over_params"] \
        == pytest.approx(1.0, rel=0.2)
    assert rep["wire_bytes_per_step"] > 0
    assert rep["off_path"]["off_path_cost_us"] < 50.0

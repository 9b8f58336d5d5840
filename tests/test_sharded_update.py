"""ZeRO-style sharded weight update (ISSUE 6, arxiv 2004.13336) on the
8-virtual-device CPU mesh: trajectory equivalence vs the replicated
SYNC path, the bitwise scatter/gather fence, the replica-lockstep
(param divergence == 0) fence, sharded optimizer-state footprint and
init-sharded guarantees, donation hygiene, warmup coverage, and the
sharded checkpoint round trip.

Equivalence note: the sharded update IS the replicated update in exact
arithmetic (scatter-sum ≡ all-reduce-sum elementwise; ``/n`` is an
exact power-of-two scale; the optimizer is elementwise on shards).
Bit-equality across the two *separately compiled* XLA programs is not
a property XLA grants — fusion/FMA choices differ per program and per
buffer shape, measured at ≤1 ulp/step on this backend — so the
trajectory test pins a tight float band while the in-program
scatter/gather-vs-pmean fence and the cross-replica param-divergence
fence assert the bit-level invariants that ARE guaranteed.
"""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork, \
    NeuralNetConfiguration
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.parallel import (FlatShardLayout,
                                         ParallelWrapper,
                                         per_device_bytes)
from jax import shard_map

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")

N = 8


def _net(seed=42, gradient_normalization=None):
    b = (NeuralNetConfiguration.builder().seed(seed)
         .updater(upd.Adam(learning_rate=0.05)))
    if gradient_normalization:
        b = b.gradient_normalization(gradient_normalization)
    conf = (b.list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _toy_data(n=256, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
    return DataSet(x, y)


def test_sharded_matches_replicated_trajectory():
    """≥10 steps of sharded-update training stay on the replicated
    SYNC trajectory leaf-for-leaf (identical in exact arithmetic;
    float-rounding band across the two XLA programs — see module
    doc), with bit-identical reported losses."""
    ds = _toy_data()
    net_a = _net()
    wa = ParallelWrapper.builder(net_a).workers(N).build()
    net_b = _net()
    wb = (ParallelWrapper.builder(net_b).workers(N)
          .sharded_update(True).build())
    wa.fit(ListDataSetIterator(ds, batch_size=64), epochs=3)   # 12 steps
    wb.fit(ListDataSetIterator(ds, batch_size=64), epochs=3)
    assert net_a.iteration == net_b.iteration == 12
    assert net_a.score_ == pytest.approx(net_b.score_, rel=1e-5,
                                         abs=1e-7)
    for lname in net_a.params:
        for k in net_a.params[lname]:
            np.testing.assert_allclose(
                np.asarray(net_a.params[lname][k]),
                np.asarray(net_b.params[lname][k]),
                rtol=1e-4, atol=1e-6, err_msg=f"{lname}/{k}")


def test_scatter_gather_grads_bitwise_equal_pmean():
    """In ONE program, the layout's reduce-scatter → mean → all-gather
    round trip is BITWISE the gradient ``pmean`` it replaces: scatter
    and all-reduce accumulate in the same order, and ``/n`` is an
    exact power-of-two scale."""
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"data": N})
    params = {"l0": {"W": jax.random.normal(jax.random.PRNGKey(0),
                                            (5, 13)),
                     "b": jnp.zeros((13,))}}
    layout = FlatShardLayout(params, N)
    rng = np.random.default_rng(3)
    g_global = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(
            size=(N,) + p.shape)).astype(p.dtype), params)

    def f(g):
        g = jax.tree.map(lambda a: a[0], g)     # this replica's grads
        pm = jax.tree.map(lambda a: jax.lax.pmean(a, "data"), g)
        rt = layout.gather(layout.scatter_mean(g, "data"), "data")
        return pm, rt

    pm, rt = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P("data"),), out_specs=(P(), P()),
        check_vma=False))(g_global)
    for a, b in zip(jax.tree.leaves(pm), jax.tree.leaves(rt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_param_replica_divergence_exactly_zero():
    """The ZeRO lockstep fence: under sharded updates the diagnostic
    step's per-replica POST-GATHER param-norm spread is exactly 0.0
    (all replicas reassemble identical params), while the PR 4
    grad-norm replica divergence stays alive (> 0: replicas see
    different shards)."""
    ds = _toy_data(n=128)
    net = _net(seed=3)
    net.monitor_numerics(every=1)
    w = ParallelWrapper(net, workers=N, sharded_update=True)
    w.fit(ListDataSetIterator(ds, batch_size=64), epochs=2)
    num = net.last_numerics
    assert set(num["param_replica_divergence"]) == set(num["grad_norm"])
    assert all(v == 0.0
               for v in num["param_replica_divergence"].values())
    assert max(num["replica_divergence"].values()) > 0
    from deeplearning4j_tpu.obs import numerics as on
    snap = on.PARAM_REPLICA_DIVERGENCE.snapshot()
    assert snap and all(v == 0.0 for v in snap.values())


def test_opt_state_born_sharded_and_one_nth_footprint():
    """The optimizer state is initialized directly as 1/N shards
    (``P('data')`` moment leaves — never materialized replicated) and
    its per-device footprint is ~1/N of the replicated layout."""
    net = _net()
    w = ParallelWrapper(net, workers=N, sharded_update=True)
    w._prepare()
    from jax.sharding import PartitionSpec as P
    sharded_leaves = [
        l for l in jax.tree.leaves(w._dp_state) if l.ndim >= 1]
    assert sharded_leaves
    for leaf in sharded_leaves:
        assert leaf.sharding.spec == P("data"), leaf.sharding
        assert len(leaf.addressable_shards) == N
        assert leaf.addressable_shards[0].data.shape[0] \
            == leaf.shape[0] // N
    rep = per_device_bytes(net.opt_state)
    sh = per_device_bytes(w._dp_state, N)
    assert 0.08 < sh / rep < 0.2, (sh, rep)      # 1/8 + scalar counts
    # the footprint gauge reflects the active (sharded) layout
    from deeplearning4j_tpu.obs.metrics import OPT_STATE_BYTES
    snap = OPT_STATE_BYTES.snapshot()
    got = [v for k, v in snap.items() if "sharded" in k]
    assert got == [sh], snap


def test_sharded_update_rejects_cross_tree_grad_norm():
    """Per-layer / global-norm gradient clipping reduces across
    elements the shard doesn't hold — refused up front, not silently
    computed over 1/N slices."""
    net = _net(gradient_normalization="ClipL2PerParamType")
    w = ParallelWrapper(net, workers=N, sharded_update=True)
    with pytest.raises(ValueError, match="sharded_update"):
        w._prepare()
    with pytest.raises(ValueError, match="SYNC"):
        ParallelWrapper(_net(), workers=N,
                        mode=ParallelWrapper.AVERAGING,
                        sharded_update=True)


def test_warmup_covers_sharded_steps_and_feeds_table():
    """``warmup()`` AOT-compiles the sharded step AND its diagnostic
    sibling from batch-sharded abstract shapes: the first real fit
    batch dispatches to the warmed executables (aot_hits), tracing
    nothing new at dispatch time."""
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.perf.warmup import WarmupSpec

    net = _net(seed=11)
    net.monitor_numerics(every=2)
    w = ParallelWrapper(net, workers=N, sharded_update=True)
    rep = w.warmup([WarmupSpec(features=(64, 4), labels=(64, 2))])
    assert rep["compiled"] == 2          # step + diag sibling
    w.fit(ListDataSetIterator(_toy_data(n=64), batch_size=64),
          epochs=2)
    st = sentry.stats()
    assert st["ParallelWrapper.sync_sharded_step"]["aot_hits"] >= 1
    assert st["ParallelWrapper.sync_sharded_diag_step"]["aot_hits"] >= 1
    # the feed table rule 4 enforces really does cover every builder
    from deeplearning4j_tpu.parallel import wrapper as wmod
    builders = {name for name in dir(ParallelWrapper)
                if name.startswith("_build_") and name.endswith("_step")}
    assert builders == set(wmod.WARMUP_FEEDS)


@pytest.mark.parametrize("mode", [ParallelWrapper.AVERAGING,
                                  ParallelWrapper.ASYNC])
def test_carried_state_donation_no_buffer_growth(mode):
    """Donation audit regression: every carried tree (params, opt
    state, layer state, accumulator state) is donated, so repeated
    steps reuse buffers instead of doubling live arrays."""
    net = _net(seed=9)
    w = ParallelWrapper(net, workers=N, mode=mode)
    w._prepare()
    x = jnp.asarray(_toy_data(n=64).features)
    y = jnp.asarray(_toy_data(n=64).labels)
    rng = jax.random.PRNGKey(0)

    def step(state):
        if mode == ParallelWrapper.ASYNC:
            p, o, a = state[:3]
            p, o, s, a, _ = w._step(p, o, state[3], a, x, y, rng)
            return (p, o, a, s)
        p, o = state[:2]
        p, o, s, _ = w._step(p, o, state[2], x, y, rng,
                             jnp.asarray(0, jnp.int32))
        return (p, o, s)

    state = w._dp_state + (net.state,)
    state = step(step(state))            # build + settle layouts
    gc.collect()
    n0 = len(jax.live_arrays())
    for _ in range(4):
        state = step(state)
    gc.collect()
    n1 = len(jax.live_arrays())
    assert n1 <= n0 + 2, (n0, n1)


def test_restore_nulled_dp_state_rebuilds_resume_exact(tmp_path):
    """``FaultTolerantTrainer._restore`` nulls ``_dp_state`` after
    restoring the net; the next ``fit`` must rebuild the shards FROM
    the restored ``net.opt_state`` (not re-init zeros) — a zip-saved
    mid-run checkpoint resumes onto the uninterrupted trajectory
    bit-exactly."""
    from deeplearning4j_tpu.serialization import ModelSerializer

    ds = _toy_data(n=64, seed=2)
    it = lambda: ListDataSetIterator(ds, batch_size=64)
    net_a = _net(seed=31)
    wa = ParallelWrapper(net_a, workers=N, sharded_update=True)
    wa.fit(it(), epochs=5)
    # zip export mid-run folds the LIVE shards (ModelSerializer
    # consults the ownership backref), not the stale init moments
    ModelSerializer.write_model(net_a, tmp_path / "mid.zip",
                                save_updater=True)
    wa.fit(it(), epochs=5)                       # uninterrupted ref
    net_b = ModelSerializer.restore_multi_layer_network(
        tmp_path / "mid.zip")
    assert any(np.any(np.asarray(l) != 0)
               for l in jax.tree.leaves(net_b.opt_state))
    wb = ParallelWrapper(net_b, workers=N, sharded_update=True)
    wb.fit(it(), epochs=5)                       # resumed 5 + 5
    for pa, pb in zip(jax.tree.leaves(net_a.params),
                      jax.tree.leaves(net_b.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb))
    # the _restore-style reset itself: next fit rebuilds, no crash
    wb._dp_state = None
    wb.fit(it(), epochs=1)
    assert np.isfinite(net_b.score_)


def test_sharded_checkpoint_roundtrip(tmp_path):
    """``save_wrapper``/``restore_wrapper``: the ZeRO optimizer shards
    checkpoint per device and restore onto the same topology (moment
    leaves come back ``P('data')``-sharded), and the resumed run
    continues the uninterrupted trajectory."""
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.serialization import ShardedCheckpointer

    ds = _toy_data(n=128, seed=5)
    it = lambda: ListDataSetIterator(ds, batch_size=64)
    net_a = _net(seed=21)
    wa = ParallelWrapper(net_a, workers=N, sharded_update=True)
    wa.fit(it(), epochs=2)                       # 4 steps
    with ShardedCheckpointer(tmp_path / "ck", async_save=False) as ck:
        ck.save_wrapper(net_a.iteration, wa, wait=True)
        wa.fit(it(), epochs=2)                   # reference: 4 more
        net_b = _net(seed=99)                    # different init
        wb = ParallelWrapper(net_b, workers=N, sharded_update=True)
        ck.restore_wrapper(wb)
    assert net_b.iteration == 4
    for leaf in jax.tree.leaves(wb._dp_state):
        if leaf.ndim >= 1:
            assert leaf.sharding.spec == P("data"), leaf.sharding
    wb.fit(it(), epochs=2)
    for lname in net_a.params:
        for k in net_a.params[lname]:
            np.testing.assert_allclose(
                np.asarray(net_a.params[lname][k]),
                np.asarray(net_b.params[lname][k]),
                rtol=1e-6, atol=1e-7, err_msg=f"{lname}/{k}")

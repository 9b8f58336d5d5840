"""Distributed tests on the virtual 8-device CPU mesh.

Reference analogs: ParallelWrapperTest (workers on CPU backend),
DelayedModelParameterServerTest-style in-process multi-node simulation
(SURVEY §4 "multi-node without a cluster").
"""
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
from deeplearning4j_tpu.parallel import (
    AdaptiveThresholdAlgorithm, EncodedGradientsAccumulator,
    ParallelInference, ParallelWrapper, decode_bitmap, decode_threshold,
    encode_bitmap, encode_threshold, make_mesh,
)
from deeplearning4j_tpu.parallel.ring_attention import (
    ring_self_attention, ulysses_attention)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _net(seed=42):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(upd.Adam(learning_rate=0.05))
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _toy_data(n=256, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y_idx = (x.sum(1) > 0).astype(int)
    y = np.eye(2, dtype=np.float32)[y_idx]
    return DataSet(x, y)

def test_make_mesh_shapes():
    m = make_mesh({"data": 4, "model": 2})
    assert m.devices.shape == (4, 2)
    m2 = make_mesh({"data": -1})
    assert m2.devices.size == len(jax.devices())
    with pytest.raises(ValueError):
        make_mesh({"data": 16})


def test_parallel_wrapper_sync_learns():
    net = _net()
    w = (ParallelWrapper.builder(net).workers(8).build())
    it = ListDataSetIterator(_toy_data(), batch_size=64)
    w.fit(it, epochs=10)
    assert net.score() < 0.3
    ds = _toy_data(64, seed=3)
    preds = np.asarray(net.output(ds.features)).argmax(1)
    assert (preds == ds.labels.argmax(1)).mean() > 0.9


def test_sync_matches_single_device_step():
    """DP over 8 devices must equal single-device full-batch training
    (same global batch, sync allreduce semantics)."""
    ds = _toy_data(64)
    net_a = _net()
    net_a.fit(ds.features, ds.labels)
    net_b = _net()
    w = ParallelWrapper.builder(net_b).workers(8).build()
    it = ListDataSetIterator(ds, batch_size=64)
    w.fit(it, epochs=1)
    for ka in net_a.params:
        for kk in net_a.params[ka]:
            np.testing.assert_allclose(
                np.asarray(net_a.params[ka][kk]),
                np.asarray(net_b.params[ka][kk]), rtol=2e-3, atol=2e-5)


def test_parallel_wrapper_averaging():
    net = _net()
    w = (ParallelWrapper.builder(net).workers(8)
         .training_mode(ParallelWrapper.AVERAGING)
         .averaging_frequency(2).build())
    it = ListDataSetIterator(_toy_data(), batch_size=64)
    w.fit(it, epochs=6)
    ds = _toy_data(64, seed=3)
    preds = np.asarray(net.output(ds.features)).argmax(1)
    assert (preds == ds.labels.argmax(1)).mean() > 0.85


def test_averaging_mode_averages_updater_state():
    """averageUpdaters=true (reference Builder default): at each
    averaging round the optimizer MOMENTS are pmean'd with the params,
    and _sync_back folds the replica mean — not replica 0's moments
    (VERDICT r3 #9)."""
    net = _net()
    w = (ParallelWrapper.builder(net).workers(8)
         .training_mode(ParallelWrapper.AVERAGING)
         .averaging_frequency(1).build())
    assert w.average_updaters        # reference default
    it = ListDataSetIterator(_toy_data(), batch_size=64)
    w.fit(it, epochs=1)
    # frequency=1: every step averaged → replicas agree on moments
    p_stack, o_stack = w._dp_state
    for leaf in jax.tree.leaves(o_stack):
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(a, np.broadcast_to(a[:1], a.shape),
                                       rtol=1e-6, atol=1e-7)
    # and the net got the replica mean
    for got, stack in zip(jax.tree.leaves(net.opt_state),
                          jax.tree.leaves(o_stack)):
        a = np.asarray(stack)
        want = a.mean(0) if np.issubdtype(a.dtype, np.floating) else a[0]
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-6, atol=1e-7)


def test_averaging_mode_updaters_opt_out():
    """average_updaters=False (reference averageUpdaters(false)):
    moments stay replica-local and _sync_back keeps replica 0's."""
    net = _net()
    w = (ParallelWrapper.builder(net).workers(8)
         .training_mode(ParallelWrapper.AVERAGING)
         .averaging_frequency(2).average_updaters(False).build())
    it = ListDataSetIterator(_toy_data(), batch_size=64)
    w.fit(it, epochs=2)
    p_stack, o_stack = w._dp_state
    # shards differ → at least one float moment leaf diverges
    diverged = any(
        np.issubdtype(np.asarray(l).dtype, np.floating)
        and not np.allclose(np.asarray(l),
                            np.broadcast_to(np.asarray(l)[:1],
                                            np.asarray(l).shape))
        for l in jax.tree.leaves(o_stack))
    assert diverged
    for got, stack in zip(jax.tree.leaves(net.opt_state),
                          jax.tree.leaves(o_stack)):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(stack)[0])


def test_parallel_wrapper_encoded():
    net = _net()
    acc = EncodedGradientsAccumulator(
        AdaptiveThresholdAlgorithm(initial_threshold=1e-4))
    w = (ParallelWrapper.builder(net).workers(8)
         .gradients_accumulator(acc).build())
    it = ListDataSetIterator(_toy_data(), batch_size=64)
    w.fit(it, epochs=10)
    ds = _toy_data(64, seed=3)
    preds = np.asarray(net.output(ds.features)).argmax(1)
    assert (preds == ds.labels.argmax(1)).mean() > 0.85


def test_parallel_wrapper_async_converges_vs_sync():
    """ASYNC mode (reference SharedTrainingMaster async exchange,
    staleness-1 peer updates + local residuals) must converge to the
    same quality as SYNC on the toy task."""
    net_async = _net()
    acc = EncodedGradientsAccumulator(
        AdaptiveThresholdAlgorithm(initial_threshold=1e-4))
    w = (ParallelWrapper.builder(net_async).workers(8)
         .training_mode(ParallelWrapper.ASYNC)
         .gradients_accumulator(acc).build())
    it = ListDataSetIterator(_toy_data(), batch_size=64)
    w.fit(it, epochs=10)

    net_sync = _net()
    ws = ParallelWrapper.builder(net_sync).workers(8).build()
    ws.fit(ListDataSetIterator(_toy_data(), batch_size=64), epochs=10)

    ds = _toy_data(64, seed=3)
    acc_async = (np.asarray(net_async.output(ds.features)).argmax(1)
                 == ds.labels.argmax(1)).mean()
    acc_sync = (np.asarray(net_sync.output(ds.features)).argmax(1)
                == ds.labels.argmax(1)).mean()
    assert acc_async > 0.85, acc_async
    assert acc_async >= acc_sync - 0.1, (acc_async, acc_sync)


def test_async_exchange_staleness_semantics():
    """Step 1 must deliver ONLY the replica's own update (peers'
    in-flight queues are empty); step 2 must deliver step-1 peer
    messages — the one-step staleness contract."""
    from jax.sharding import Mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    acc = EncodedGradientsAccumulator(
        AdaptiveThresholdAlgorithm(initial_threshold=0.5))
    devs = np.array(jax.devices()[:2])
    mesh = Mesh(devs, ("data",))
    g = jnp.stack([jnp.full((4,), 1.0), jnp.full((4,), -1.0)])  # per-dev

    def two_steps(g):
        g = g[0]
        st = acc.init_async_state(g)
        out1, st = acc.exchange_async(g, st, "data")
        out2, st = acc.exchange_async(jnp.zeros_like(g), st, "data")
        return out1[None], out2[None]

    o1, o2 = shard_map(
        two_steps, mesh=mesh, in_specs=(P("data"),),
        out_specs=(P("data"), P("data")), check_vma=False)(g)
    tau = 0.5
    # step 1: own update only, averaged over 2 devices: ±tau/2
    np.testing.assert_allclose(np.asarray(o1[0]), tau / 2, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o1[1]), -tau / 2, atol=1e-6)
    # step 2: peer's step-1 message arrives (grad now zero, residual
    # 1-tau stays below the adapted threshold)
    np.testing.assert_allclose(np.asarray(o2[0]),
                               np.asarray(-o2[1]), atol=1e-6)
    assert abs(float(o2[0][0])) > 0  # something did arrive late


def test_threshold_encode_decode_roundtrip():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(64,)) * 0.01)
    tau = 0.005
    sign, residual = encode_threshold(g, tau)
    decoded = decode_threshold(sign, tau)
    np.testing.assert_allclose(np.asarray(decoded + residual),
                               np.asarray(g), rtol=1e-6)
    # sparsity: only |g|>tau encoded
    assert (np.asarray(sign) != 0).sum() == (np.abs(np.asarray(g)) >
                                             tau).sum()


def test_bitmap_pack_roundtrip():
    rng = np.random.default_rng(1)
    sign = jnp.asarray(rng.choice([-1, 0, 1], size=(37,)), jnp.int8)
    pos, neg = encode_bitmap(sign)
    # 16x compression: 2 bitmaps of ceil(37/8)=5 bytes vs 148 bytes f32
    assert pos.size == 5 and neg.size == 5
    out = decode_bitmap(pos, neg, 37)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(sign))


def test_ring_attention_matches_full():
    mesh = make_mesh({"seq": 8})
    b, t, h, d = 2, 32, 4, 8
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, h, d))
    v = jax.random.normal(kv, (b, t, h, d))
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    full = scaled_dot_attention(q, k, v)
    ring = ring_self_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ring),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_masked():
    mesh = make_mesh({"seq": 8})
    b, t, h, d = 1, 16, 2, 4
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (b, t, h, d))
    mask = (jnp.arange(t)[None, :] < 10).astype(jnp.float32)
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    full = scaled_dot_attention(q, q, q, mask=mask)
    ring = ring_self_attention(q, q, q, mesh, mask=mask)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ring),
                               rtol=2e-4, atol=2e-5)


# --- fit stages the next batch onto the mesh while a step runs --------------

_STAGING_MODES = {
    "sync": {},
    "sync-sharded": {"sharded_update": True},
    "sync-overlap": {"sharded_update": True, "gather_overlap": True},
    "encoded": {"mode": ParallelWrapper.ENCODED},
    "async": {"mode": ParallelWrapper.ASYNC},
    "averaging": {"mode": ParallelWrapper.AVERAGING,
                  "averaging_frequency": 2},
}
#: rows of each batch an epoch feeds: all even, or with a ragged one
#: (trimmed to 56 over 8 workers) and one smaller than the mesh
#: (dropped with a warning) in the middle
_STAGING_PLANS = {"even": (64, 64, 64, 64, 64),
                  "ragged-dropped": (64, 64, 60, 5, 64, 64)}


def _plan_batches(plan):
    ds, out, at = _toy_data(sum(plan)), [], 0
    for n in plan:
        out.append(DataSet(ds.features[at:at + n], ds.labels[at:at + n]))
        at += n
    return out


class _StepLog:
    """A listener that keeps what each step showed it, in order."""

    def __init__(self):
        self.calls = []

    def iteration_done(self, net, iteration, epoch):
        self.calls.append((iteration, net.score_))


def _spied(monkeypatch, **kw):
    """A wrapper whose staging calls and step arguments are recorded:
    ``puts`` holds (type of the source, its shape, the sharding asked
    for) of every ``jax.device_put``, ``fed`` the batch arrays each
    step was called with."""
    net = _net()
    net.listeners.append(_StepLog())
    w = ParallelWrapper(net, workers=8, prefetch_buffer=0, **kw)
    w._ensure_ready()
    puts, fed = [], []
    real_put, real_step = jax.device_put, w._step

    def put(a, sharding=None, **k):
        puts.append((type(a), np.shape(a), sharding))
        return real_put(a, sharding, **k)

    def step(*args):
        # every builder's signature ends (..., x, y, rng[, iteration])
        at = -4 if w.mode == ParallelWrapper.AVERAGING else -3
        fed.append(args[at:at + 2])
        return real_step(*args)

    monkeypatch.setattr(jax, "device_put", put)
    w._step = step
    return w, net, puts, fed


def _fit_records():
    from deeplearning4j_tpu.obs import trace
    return [r for r in trace.records() if r.name == "ParallelWrapper.fit"]


@pytest.mark.parametrize("plan", sorted(_STAGING_PLANS))
@pytest.mark.parametrize("mode", sorted(_STAGING_MODES))
def test_fit_stages_ahead_and_trains_as_one_call_a_batch(
        mode, plan, monkeypatch):
    """One ``fit`` over the epoch (every batch but the first staged
    while the step before it ran) against the same batches fed one
    ``fit`` call each (which never stages ahead): same losses, same
    listener calls, same parameters; the step reads arrays laid over
    the mesh as it declares them, made straight from host memory."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    batches = _plan_batches(_STAGING_PLANS[plan])
    kept = [b for b in batches if b.num_examples() >= 8]
    w, net, puts, fed = _spied(monkeypatch, **_STAGING_MODES[mode])
    seen = len(_fit_records())
    w.fit(batches)
    recs, puts = _fit_records()[seen:], list(puts)

    w1, net1, _, _ = _spied(monkeypatch, **_STAGING_MODES[mode])
    seen = len(_fit_records())
    for b in batches:
        w1.fit([b])
    recs1 = _fit_records()[seen:]

    log, log1 = net.listeners[0].calls, net1.listeners[0].calls
    assert [i for i, _ in log] == list(range(1, len(kept) + 1))
    assert log == log1                  # order, iteration, score: equal
    assert net.iteration == net1.iteration == len(kept)
    for a, b in zip(jax.tree.leaves(net.params),
                    jax.tree.leaves(net1.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # what each step was given: the batch's rows in order, trimmed to
    # what 8 workers divide, an eighth on each device
    rows = NamedSharding(w.mesh, P("data"))
    assert len(fed) == len(kept)
    for (x, y), b in zip(fed, kept):
        n = b.num_examples() - b.num_examples() % 8
        np.testing.assert_array_equal(np.asarray(x), b.features[:n])
        np.testing.assert_array_equal(np.asarray(y), b.labels[:n])
        for a in (x, y):
            assert a.sharding.is_equivalent_to(rows, a.ndim)
            assert sorted(s.data.shape[0]
                          for s in a.addressable_shards) == [n // 8] * 8
    # ... each made by ONE put of the host array over the mesh: no put
    # of a batch onto a single device, none of a device array
    batch_puts = [p for p in puts if p[1][:1] and p[1][0] >= 8
                  and len(p[1]) == 2]
    assert len(batch_puts) == 2 * len(kept)
    assert all(t is np.ndarray and sh == rows for t, _, sh in batch_puts)

    # the record: same phases, what was enqueued in each iteration,
    # and whether the step ran on a batch staged during the one before
    assert all(r.phases == ("h2d", "dispatch", "collective_sync")
               for r in recs + recs1)
    size = lambda b: (b.features[:b.num_examples() // 8 * 8].nbytes
                      + b.labels[:b.num_examples() // 8 * 8].nbytes)
    assert sum(r.counts["bytes"] for r in recs) == sum(map(size, kept))
    assert [r.counts["bytes"] for r in recs1] == [size(b) for b in kept]
    assert not any(r.counts["staged_ahead"] for r in recs1)
    ahead = [r.counts["staged_ahead"] for r in recs]
    if plan == "even":
        assert ahead == [0, 1, 1, 1, 1]         # N - 1 of N
        assert [r.counts["bytes"] for r in recs] == \
            [2 * size(kept[0])] + [size(kept[0])] * 3 + [0]
    else:
        # the dropped batch was the one pulled ahead, so the step
        # after it staged its own
        assert ahead == [0, 1, 1, 0, 1]


@pytest.mark.parametrize("was_on", [True, False])
def test_fit_holds_the_collector_from_launch_to_enqueue(
        was_on, monkeypatch):
    """No garbage collection between a step's launch and the enqueueing
    of the batch staged ahead; the collector is left as it was found,
    also when the step raises."""
    import gc
    from deeplearning4j_tpu.resilience import faults
    w, net, _, _ = _spied(monkeypatch)
    at_launch, at_stage = [], []
    launch, stage = w._step, w._stage

    def step(*args):
        at_launch.append(gc.isenabled())
        if len(at_launch) == 5:
            raise faults.InjectedFault("in the launch")
        return launch(*args)

    def staged(ds, b_local):
        at_stage.append(gc.isenabled())
        return stage(ds, b_local)

    w._step, w._stage = step, staged
    (gc.enable if was_on else gc.disable)()
    try:
        w.fit(_plan_batches((64, 64, 64)))
        assert gc.isenabled() == was_on
        # the call's own first batch is staged before the launch, the
        # two staged ahead inside the hold
        assert at_launch == [False] * 3
        assert at_stage == [was_on, False, False]
        with pytest.raises(faults.InjectedFault):
            w.fit(_plan_batches((64, 64, 64)))
        assert gc.isenabled() == was_on
    finally:
        gc.enable()


class _Counted:
    """An iterable of batches that counts how many were pulled."""

    def __init__(self, batches):
        self.batches, self.pulled = batches, 0

    def __iter__(self):
        for b in self.batches:
            self.pulled += 1
            yield b


class _FailsAt:
    def __init__(self, iteration):
        self.iteration = iteration

    def iteration_done(self, net, iteration, epoch):
        if iteration == self.iteration:
            raise FloatingPointError(f"step {iteration} went wrong")


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["sharded", "gather-overlap"])
@pytest.mark.parametrize("site", ["worker_step", "listener"])
def test_fit_failure_drops_the_batch_staged_ahead(site, overlap):
    """A failure in step 3 raises as before, has advanced the iterator
    by at most one batch beyond the last step launched, and leaves a
    wrapper whose next ``fit`` trains from what step 2 (``worker_step``:
    the fault fires before step 3's dispatch, step 2 is in flight and
    booked first) or step 4 (a listener of step 3 runs with step 4 on
    the chips: it is read and booked, then the error raised) left."""
    from deeplearning4j_tpu.resilience import faults
    batches = _plan_batches((64,) * 6)
    kw = {"sharded_update": True, "gather_overlap": overlap,
          "prefetch_buffer": 0}
    net = _net()
    w = ParallelWrapper(net, workers=8, **kw)
    feed = _Counted(batches)
    if site == "worker_step":
        done = 2
        with faults.active("worker_step:error=InjectedFault:nth=3:max=1"):
            with pytest.raises(faults.InjectedFault):
                w.fit(feed)
    else:
        done = 4
        net.listeners.append(_FailsAt(3))
        with pytest.raises(FloatingPointError, match="step 3"):
            w.fit(feed)
        net.listeners.clear()
    assert net.iteration == done
    assert done <= feed.pulled <= done + 1      # at most one beyond
    assert not w._params_stale          # fit's finally materialised

    ref = _net()
    wr = ParallelWrapper(ref, workers=8, **kw)
    wr.fit(batches[:done])
    for a, b in zip(jax.tree.leaves(net.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    w.fit(batches[done:done + 2])
    wr.fit(batches[done:done + 2])
    assert net.iteration == ref.iteration == done + 2
    assert net.score_ == ref.score_
    for a, b in zip(jax.tree.leaves(net.params),
                    jax.tree.leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_defers_the_iterators_error_until_the_step_is_booked():
    """An iterator that fails while the batch after step 2 is pulled
    (now during step 2) still raises after step 2's loss, bookkeeping
    and listeners, as when the loop pulled it afterwards."""
    def feed():
        yield from _plan_batches((64, 64))
        raise OSError("the reader lost its file")

    net = _net()
    log = _StepLog()
    net.listeners.append(log)
    w = ParallelWrapper(net, workers=8, prefetch_buffer=0)
    with pytest.raises(OSError, match="lost its file"):
        w.fit(feed())
    assert net.iteration == 2
    assert [i for i, _ in log.calls] == [1, 2]
    assert log.calls[-1][1] == net.score_


# --- fit launches step n+1 before it reads step n's loss --------------------

def _dropout_net(seed=42):
    """A net whose step depends on its ``rng`` and carries layer state
    (batch norm), so a wrong fold or a wrong order shows in the bits."""
    from deeplearning4j_tpu.nn.layers import BatchNormalization
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(upd.Nesterovs(learning_rate=0.05, momentum=0.9))
            .list()
            .layer(DenseLayer(n_out=16, activation="tanh", dropout=0.25))
            .layer(BatchNormalization())
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    return MultiLayerNetwork(conf).init()


def _logged_wrapper(net=None, **kw):
    """A wrapper over a net with a step log, and the rng every launch
    was handed (every builder's signature ends ``x, y, rng[, it]``)."""
    net = _dropout_net() if net is None else net
    net.listeners.append(_StepLog())
    kw.setdefault("prefetch_buffer", 0)
    w = ParallelWrapper(net, workers=8, **kw)
    w._ensure_ready()
    rngs, real = [], w._step

    def step(*args):
        rngs.append(np.asarray(
            args[-2 if w.mode == ParallelWrapper.AVERAGING else -1]))
        return real(*args)

    w._step = step
    return w, net, rngs


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


_AHEAD_MODES = {k: _STAGING_MODES[k]
                for k in ("sync", "sync-sharded", "encoded", "averaging")}


@pytest.mark.parametrize("mode", sorted(_AHEAD_MODES))
def test_run_ahead_order_equals_the_blocking_order(mode):
    """One call of n batches (every step but the first launched before
    its predecessor's loss was read) against n calls of one batch (a
    call's first step finds the pipeline empty: the blocking order by
    construction, same step program): bit-equal losses, the same
    listener sequence, bit-equal final state, and every step's rng the
    one its iteration number folds."""
    batches = _plan_batches((64,) * 6)
    w, net, rngs = _logged_wrapper(**_AHEAD_MODES[mode])
    seen = len(_fit_records())
    w.fit(batches)
    recs = _fit_records()[seen:]
    w1, net1, rngs1 = _logged_wrapper(**_AHEAD_MODES[mode])
    for b in batches:
        w1.fit([b])

    assert [r.counts["ahead"] for r in recs] == [0, 1, 1, 1, 1, 1]
    log, log1 = net.listeners[0].calls, net1.listeners[0].calls
    assert [i for i, _ in log] == [1, 2, 3, 4, 5, 6]
    assert log == log1                  # iteration and score, in order
    assert len(set(s for _, s in log)) == 6     # and they do differ
    assert net.iteration == net1.iteration == 6
    assert net.score_ == net1.score_ == log[-1][1]
    _assert_trees_equal(net.params, net1.params)
    _assert_trees_equal(net.opt_state, net1.opt_state)
    _assert_trees_equal(net.state, net1.state)
    _assert_trees_equal(w._dp_state, w1._dp_state)
    # step i (0-based) folds i, not net.iteration at its launch (i - 1)
    want = [np.asarray(jax.random.fold_in(
        jax.random.PRNGKey(net.conf.seed), i)) for i in range(6)]
    for got in (rngs, rngs1):
        assert len(got) == 6
        for g, r in zip(got, want):
            np.testing.assert_array_equal(g, r)


def test_ahead_count_and_counter_follow_the_pipeline():
    """``ahead`` is 0 on a call's and an epoch's first step and 1 on
    the rest, the counter grows by their sum, the last step of each
    epoch is read outside a launch with its listeners seeing its own
    epoch, and nothing is in flight when ``fit`` returns."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.obs import trace

    class Epochs(_StepLog):
        def iteration_done(self, net, iteration, epoch):
            self.calls.append((iteration, epoch, net.epoch))

    net = _dropout_net()
    net.listeners.append(Epochs())
    w = ParallelWrapper(net, workers=8, prefetch_buffer=0)
    worker = f"proc{jax.process_index()}"
    count = lambda: obs.metrics.WORKER_AHEAD.labels(worker=worker).value
    steps = lambda: obs.metrics.WORKER_STEP.labels(worker=worker).count
    c0, s0, seen = count(), steps(), len(_fit_records())
    sync = lambda: obs.metrics.WORKER_SYNC.labels(worker=worker).get()
    y0 = sync()
    w.fit(_plan_batches((64,) * 4), epochs=2)
    recs = _fit_records()[seen:]
    assert [r.counts["ahead"] for r in recs] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert [r.counts["staged_ahead"] for r in recs] == \
        [0, 1, 1, 1, 0, 1, 1, 1]
    assert count() - c0 == 6 and steps() - s0 == 8
    # an epoch's last step is read outside a launch, in no record's
    # collective_sync: its wait joins the sync counter all the same
    in_records = sum(r.stamps[3] - r.stamps[2] for r in recs)
    assert sync() - y0 > in_records
    # a record's collective_sync is the read made in ITS iteration:
    # zero long where the pipeline was empty
    for r in recs:
        t2, t3 = r.stamps[2], r.stamps[3]
        assert (t3 == t2) == (r.counts["ahead"] == 0)
    assert w._flight is None
    assert net.iteration == 8 and net.epoch == 2
    assert net.listeners[0].calls == \
        [(i, 0, 0) for i in (1, 2, 3, 4)] + [(i, 1, 1) for i in (5, 6, 7, 8)]

    w.fit(_plan_batches((64,)))          # a call of one step
    assert [r.counts["ahead"] for r in _fit_records()[seen:]][8:] == [0]
    assert w._flight is None and net.iteration == 9


class _LossThatFails:
    """A step's loss whose read raises, as a device fault surfaces."""

    def __float__(self):
        raise RuntimeError("the device lost step 3")


@pytest.mark.parametrize("site", ["worker_step", "read", "listener",
                                  "iterator"])
def test_fault_with_a_step_in_flight(site):
    """An error of the host's side finds a sound step on the chips: it
    is read, booked and shown to the listeners, then the error raised,
    in the order the blocking loop had. A step whose READ raises takes
    the step launched on its outputs with it: no record, no listener
    call, no iteration."""
    from deeplearning4j_tpu.resilience import faults
    batches = _plan_batches((64,) * 6)
    w, net, rngs = _logged_wrapper()
    seen = len(_fit_records())
    if site == "worker_step":
        # fires at the top of iteration 3, step 2 in flight
        booked, launched, error = 2, 2, faults.InjectedFault
        with faults.active("worker_step:error=InjectedFault:nth=3:max=1"):
            with pytest.raises(error):
                w.fit(batches)
    elif site == "read":
        # step 3's read raises in iteration 4, step 4 launched
        booked, launched, error = 2, 4, RuntimeError
        spied = w._step

        def step(*args):
            out = spied(*args)
            return out[:-1] + (_LossThatFails(),) \
                if len(rngs) == 3 else out

        w._step = step
        with pytest.raises(error, match="lost step 3"):
            w.fit(batches)
    elif site == "listener":
        # step 3's listener raises with step 4 on the chips
        booked, launched, error = 4, 4, FloatingPointError
        net.listeners.append(_FailsAt(3))
        with pytest.raises(error, match="step 3"):
            w.fit(batches)
    else:
        # the pull for batch 4 raises under step 3, step 2 unread
        booked, launched, error = 3, 3, OSError

        def feed():
            yield from batches[:3]
            raise OSError("the reader lost its file")

        with pytest.raises(error, match="lost its file"):
            w.fit(feed())
    recs = _fit_records()[seen:]
    assert len(rngs) == launched
    assert w._flight is None
    assert net.iteration == booked
    assert [i for i, _ in net.listeners[0].calls] == \
        list(range(1, booked + 1))
    # a record a step that was read or drained; the step dropped unread
    # (and, in its iteration, unrecorded) leaves none
    assert len(recs) == (3 if site == "read" else booked)
    if site != "read":
        # what the host's error left is what `booked` blocking steps
        # leave, and training goes on from there
        ref, netr, _ = _logged_wrapper()
        for b in batches[:booked]:
            ref.fit([b])
        assert net.listeners[0].calls == netr.listeners[0].calls
        _assert_trees_equal(net.params, netr.params)
        _assert_trees_equal(net.opt_state, netr.opt_state)
        net.listeners[:] = net.listeners[:1]
        w.fit(batches[booked:booked + 2])
        ref.fit(batches[booked:booked + 2])
        assert net.listeners[0].calls == netr.listeners[0].calls
        _assert_trees_equal(net.params, netr.params)


@pytest.mark.parametrize("site", ["read", "listener", "interrupt"])
def test_drain_that_fails_keeps_the_first_error(site):
    """The error that stopped the loop is the one the caller sees
    (a retry policy classifies it), whatever the drain of the step in
    flight raises in its turn: that rides on it as a note. An
    interrupt drains nothing: no wait, no listener, the flight is
    dropped unread."""
    from deeplearning4j_tpu.resilience import faults
    batches = _plan_batches((64,) * 6)
    w, net, rngs = _logged_wrapper()
    if site == "read":
        # the worker_step site fires at the top of iteration 3 with
        # step 2 in flight, and step 2's read then raises
        spied = w._step

        def step(*args):
            out = spied(*args)
            return out[:-1] + (_LossThatFails(),) \
                if len(rngs) == 2 else out

        w._step = step
        with faults.active("worker_step:error=ConnectionError:nth=3:max=1"):
            with pytest.raises(ConnectionError) as err:
                w.fit(batches)
        assert any("lost step 3" in n for n in err.value.__notes__)
        booked, calls = 1, [1]
    elif site == "listener":
        # step 3's listener raises with step 4 on the chips; the drain
        # books step 4 and calls the listeners once more: one raises
        class FailsFrom(_FailsAt):
            def iteration_done(self, net, iteration, epoch):
                if iteration >= self.iteration:
                    raise FloatingPointError(f"step {iteration} went wrong")

        net.listeners.append(FailsFrom(3))
        with pytest.raises(FloatingPointError, match="step 3") as err:
            w.fit(batches)
        assert any("step 4" in n for n in err.value.__notes__)
        booked, calls = 4, [1, 2, 3, 4]
    else:
        class Interrupts:
            def iteration_done(self, net, iteration, epoch):
                if iteration == 3:
                    raise KeyboardInterrupt

        net.listeners.append(Interrupts())
        with pytest.raises(KeyboardInterrupt):
            w.fit(batches)
        assert len(rngs) == 4           # step 4 was on the chips
        booked, calls = 3, [1, 2, 3]
    assert w._flight is None
    assert net.iteration == booked
    assert [i for i, _ in net.listeners[0].calls] == calls


class _SavesEvery:
    """A listener that reads the net's state at its cadence and says
    so, as ``CheckpointListener`` does."""

    def __init__(self, every, says_so=True):
        self.every, self.saved = every, {}
        if says_so:
            self.reads_state = lambda it: it % self.every == 0

    def iteration_done(self, net, iteration, epoch):
        if iteration % self.every == 0:
            self.saved[iteration] = jax.tree.map(
                np.asarray, (net.params, net.opt_state, net.state))


@pytest.mark.parametrize("mode", ["sync", "sync-sharded"])
def test_listener_that_reads_state_finds_its_own_step(mode):
    """Where a listener says that it reads the net's state at an
    iteration, no step runs ahead of that one: what it saves at
    iteration k is what k steps left, the step after it starts from
    an empty pipeline, and the training is the blocking order's."""
    from deeplearning4j_tpu.train.listeners import (CheckpointListener,
                                                    EvaluativeListener,
                                                    TrainingListener)
    assert not TrainingListener().reads_state(3)
    ck = CheckpointListener("/tmp/unused-by-this-test",
                            save_every_n_iterations=3)
    ev = EvaluativeListener(None, frequency_iters=2)
    assert [ck.reads_state(i) for i in (2, 3, 6)] == [False, True, True]
    assert [ev.reads_state(i) for i in (2, 3, 6)] == [True, False, True]
    assert not EvaluativeListener(None).reads_state(2)

    batches = _plan_batches((64,) * 7)
    w, net, rngs = _logged_wrapper(**_AHEAD_MODES[mode])
    saves = _SavesEvery(3)
    net.listeners.append(saves)
    seen = len(_fit_records())
    w.fit(batches)
    assert [r.counts["ahead"] for r in _fit_records()[seen:]] == \
        [0, 1, 1, 0, 1, 1, 0]
    ref, netr, _ = _logged_wrapper(**_AHEAD_MODES[mode])
    quiet = _SavesEvery(3, says_so=False)
    netr.listeners.append(quiet)
    for b in batches:
        ref.fit([b])
    assert sorted(saves.saved) == sorted(quiet.saved) == [3, 6]
    for k in (3, 6):
        _assert_trees_equal(saves.saved[k], quiet.saved[k])
    assert net.listeners[0].calls == netr.listeners[0].calls
    _assert_trees_equal(net.params, netr.params)
    _assert_trees_equal(net.opt_state, netr.opt_state)


class _Elastic:
    """The calls an elastic context sees, in order."""

    def __init__(self):
        self.calls = []

    def pre_step(self, iteration):
        self.calls.append(("pre", iteration))

    def run(self, fn):
        self.calls.append(("run",))
        return fn()

    def sync(self, value):
        self.calls.append(("sync",))
        return float(value)

    def post_step(self, iteration, loss):
        self.calls.append(("post", iteration, loss))


@pytest.mark.parametrize("reader", ["elastic", "diagnostic"])
def test_modes_that_read_each_step_keep_no_step_in_flight(reader):
    """Under an elastic context every step is read before the next is
    launched, ``pre_step``/``post_step`` bracket each step as before;
    a diagnostic step that is due drains the step in flight, runs
    alone and is read at once. Both train as one call a step does."""
    batches = _plan_batches((64,) * 6)
    net = _dropout_net()
    if reader == "diagnostic":
        net.monitor_numerics(every=3)
    w, net, rngs = _logged_wrapper(net)
    if reader == "elastic":
        w.elastic = _Elastic()
    else:
        diag = w._ensure_diag_step(net._numerics)
        w._diag_step = lambda *a: (rngs.append(np.asarray(a[-1])),
                                   diag(*a))[1]
    seen = len(_fit_records())
    order = []              # steps launched when step i's listeners run

    class Launched:
        def iteration_done(self, net, iteration, epoch):
            order.append(len(rngs))

    net.listeners.append(Launched())
    w.fit(batches)
    ahead = [r.counts["ahead"] for r in _fit_records()[seen:]]
    if reader == "elastic":
        assert ahead == [0] * 6
        # step i's listeners ran before step i + 1 was launched
        assert order == [1, 2, 3, 4, 5, 6]
        log = net.listeners[0].calls
        want = []
        for i in range(6):
            want += [("pre", i), ("run",), ("sync",),
                     ("post", i, log[i][1])]
        assert w.elastic.calls == want
    else:
        # iterations 2 and 5 (the 3rd and 6th step) are diagnostic:
        # nothing in flight before, in or after them
        assert ahead == [0, 1, 0, 0, 1, 0]
        assert order == [2, 2, 3, 5, 5, 6]
        assert net.last_numerics["iteration"] == 6
    assert w._flight is None and net.iteration == 6
    # the same steps, each read before the next by construction
    netr = _dropout_net()
    if reader == "diagnostic":
        netr.monitor_numerics(every=3)
    ref, netr, _ = _logged_wrapper(netr)
    for b in batches:
        ref.fit([b])
    assert net.listeners[0].calls == netr.listeners[0].calls
    _assert_trees_equal(net.params, netr.params)
    _assert_trees_equal(net.state, netr.state)


def test_lockstep_budget_is_never_overrun_by_a_launch(monkeypatch):
    """Multi-host: with a budget of 3 steps an epoch agreed across the
    processes, exactly 3 steps are launched however many batches the
    local iterator holds, and the third is read before the epoch ends."""
    from jax.experimental import multihost_utils as mhu
    replies = iter([3, 10 ** 6])        # the peers': step count, batch

    def allgather(a):
        return np.asarray([int(np.asarray(a)[0]), next(replies)])

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(mhu, "process_allgather", allgather)
    w, net, rngs = _logged_wrapper()
    seen = len(_fit_records())
    w.fit(_plan_batches((64,) * 5))     # a list: sized, as it must be
    assert len(rngs) == 3               # launches
    assert [r.counts["ahead"] for r in _fit_records()[seen:]] == [0, 1, 1]
    assert net.iteration == 3 and w._flight is None
    assert [i for i, _ in net.listeners[0].calls] == [1, 2, 3]


def _multi_io_graph(seed=1):
    from deeplearning4j_tpu.nn import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.vertices import ElementWiseVertex
    from deeplearning4j_tpu.nn import updaters as upd
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(upd.Adam(learning_rate=0.05))
            .graph_builder()
            .add_inputs("a", "b")
            .add_layer("da", DenseLayer(n_out=8, activation="tanh"), "a")
            .add_layer("db", DenseLayer(n_out=8, activation="tanh"), "b")
            .add_vertex("sum", ElementWiseVertex(op="add"), "da", "db")
            .add_layer("out1", OutputLayer(n_out=2, activation="softmax",
                                           loss="mcxent"), "sum")
            .add_layer("out2", OutputLayer(n_out=1,
                                           activation="identity",
                                           loss="mse"), "sum")
            .set_outputs("out1", "out2")
            .set_input_types(a=InputType.feed_forward(3),
                             b=InputType.feed_forward(3))
            .build())
    return ComputationGraph(conf).init()


def _multi_io_data(n=256, batch=32):
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    rng = np.random.default_rng(0)
    xa = rng.normal(size=(n, 3)).astype(np.float32)
    xb = rng.normal(size=(n, 3)).astype(np.float32)
    y1 = np.eye(2, dtype=np.float32)[((xa + xb).sum(1) > 0).astype(int)]
    y2 = (xa - xb).sum(1, keepdims=True).astype(np.float32)
    return [MultiDataSet([xa[i:i + batch], xb[i:i + batch]],
                         [y1[i:i + batch], y2[i:i + batch]])
            for i in range(0, n, batch)]


@pytest.mark.parametrize("mode", [ParallelWrapper.SYNC,
                                  ParallelWrapper.ENCODED,
                                  ParallelWrapper.AVERAGING,
                                  ParallelWrapper.ASYNC])
def test_parallel_wrapper_multi_io_graph(mode):
    """DP over a 2-input/2-output ComputationGraph in all four modes
    (VERDICT r2 #5 — the reference ParallelWrapper handles arbitrary
    ComputationGraphs): every feature/label leaf shards over the data
    axis."""
    net = _multi_io_graph()
    data = _multi_io_data()
    wrapper = ParallelWrapper(net, mode=mode, averaging_frequency=2,
                              prefetch_buffer=0)
    wrapper.fit(data, epochs=4)
    assert np.isfinite(net.score_)
    assert net.score_ < 1.0, net.score_
    # trained params still produce well-formed multi-output inference
    o1, o2 = net.output(data[0].features[0], data[0].features[1])
    assert o1.shape == (32, 2) and o2.shape == (32, 1)


def test_training_masters_multi_io_graph():
    """Both TrainingMaster strategies drive a multi-io graph (single
    process; the cross-process path shares the same wrapper step)."""
    from deeplearning4j_tpu.parallel import (
        ParameterAveragingTrainingMaster, SharedTrainingMaster)
    from deeplearning4j_tpu.parallel.master import SparkComputationGraph
    for master in (ParameterAveragingTrainingMaster.Builder(32)
                   .averaging_frequency(2).build(),
                   SharedTrainingMaster.Builder(32).build()):
        net = _multi_io_graph()
        trainer = SparkComputationGraph(net, master)
        trainer.fit(_multi_io_data(), epochs=3)
        assert np.isfinite(net.score_) and net.score_ < 1.2


def test_do_evaluation_multi_io_graph():
    """doEvaluation over a 2-input/2-output graph: list features feed
    output(*x), evaluation runs on the first output/label pair."""
    from deeplearning4j_tpu.parallel import \
        ParameterAveragingTrainingMaster
    from deeplearning4j_tpu.parallel.master import SparkComputationGraph
    from deeplearning4j_tpu.eval_.evaluation import Evaluation
    net = _multi_io_graph()
    data = _multi_io_data(n=64, batch=32)
    trainer = SparkComputationGraph(
        net, ParameterAveragingTrainingMaster.Builder(32).build())
    ev, = trainer.do_evaluation(data, Evaluation())
    assert ev.count == 64
    assert 0.0 <= ev.accuracy() <= 1.0


def test_ring_attention_causal_matches_full():
    """Causal ring attention (VERDICT r2 #2): per-ring-step block
    offsets must land the causal diagonal exactly — the long-context
    causal-LM training path."""
    mesh = make_mesh({"seq": 8})
    b, t, h, d = 2, 32, 4, 8
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, h, d))
    v = jax.random.normal(kv, (b, t, h, d))
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    full = scaled_dot_attention(q, k, v, causal=True)
    ring = ring_self_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(full), np.asarray(ring),
                               rtol=2e-4, atol=1e-5)


def test_ring_attention_causal_gradients_match():
    """Backward ring (dk/dv accumulators traveling with their kv block)
    must match autodiff through dense causal attention."""
    mesh = make_mesh({"seq": 8})
    b, t, h, d = 1, 32, 2, 8
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, h, d))
    v = jax.random.normal(kv, (b, t, h, d))
    co = jax.random.normal(kc, (b, t, h, d))
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention

    g_ring = jax.grad(
        lambda q, k, v: jnp.sum(
            ring_self_attention(q, k, v, mesh, causal=True) * co),
        argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(
        lambda q, k, v: jnp.sum(
            scaled_dot_attention(q, k, v, causal=True) * co),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-5)


def test_ring_attention_masked_gradients_match():
    mesh = make_mesh({"seq": 8})
    b, t, h, d = 1, 16, 2, 4
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (b, t, h, d))
    co = jax.random.normal(jax.random.PRNGKey(6), (b, t, h, d))
    mask = (jnp.arange(t)[None, :] < 11).astype(jnp.float32)
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention

    g_ring = jax.grad(lambda x: jnp.sum(
        ring_self_attention(x, x, x, mesh, mask=mask) * co))(q)
    g_full = jax.grad(lambda x: jnp.sum(
        scaled_dot_attention(x, x, x, mask=mask) * co))(q)
    np.testing.assert_allclose(np.asarray(g_ring), np.asarray(g_full),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_causal_masked():
    """Causal + key-mask together (padded causal LM batch)."""
    mesh = make_mesh({"seq": 8})
    b, t, h, d = 2, 24, 2, 4
    q = jax.random.normal(jax.random.PRNGKey(7), (b, t, h, d))
    mask = (jnp.arange(t)[None, :]
            < jnp.asarray([[24], [17]])).astype(jnp.float32)
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    full = scaled_dot_attention(q, q, q, mask=mask, causal=True)
    ring = ring_self_attention(q, q, q, mesh, mask=mask, causal=True)
    valid = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(full) * valid,
                               np.asarray(ring) * valid,
                               rtol=2e-4, atol=2e-5)


def test_zigzag_ring_matches_dense_causal():
    """Load-balanced zigzag layout: permute → distributed causal
    attention → unpermute must equal dense causal attention in the
    original order (fwd)."""
    from deeplearning4j_tpu.parallel import (
        zigzag_permute, zigzag_ring_self_attention, zigzag_unpermute)
    mesh = make_mesh({"seq": 8})
    n, (b, t, h, d) = 8, (2, 64, 2, 8)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, h, d))
    v = jax.random.normal(kv, (b, t, h, d))
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    want = scaled_dot_attention(q, k, v, causal=True)
    zz = zigzag_ring_self_attention(
        zigzag_permute(q, n), zigzag_permute(k, n),
        zigzag_permute(v, n), mesh)
    got = zigzag_unpermute(zz, n)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-4, atol=1e-5)


def test_zigzag_ring_gradients_match():
    from deeplearning4j_tpu.parallel import (
        zigzag_permute, zigzag_ring_self_attention, zigzag_unpermute)
    mesh = make_mesh({"seq": 8})
    n, (b, t, h, d) = 8, (1, 32, 2, 8)
    q = jax.random.normal(jax.random.PRNGKey(10), (b, t, h, d))
    co = jax.random.normal(jax.random.PRNGKey(11), (b, t, h, d))
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention

    def loss_zz(x):
        xz = zigzag_permute(x, n)
        o = zigzag_ring_self_attention(xz, xz, xz, mesh)
        return jnp.sum(zigzag_unpermute(o, n) * co)

    def loss_dense(x):
        return jnp.sum(scaled_dot_attention(x, x, x, causal=True) * co)

    g_zz = jax.grad(loss_zz)(q)
    g_d = jax.grad(loss_dense)(q)
    np.testing.assert_allclose(np.asarray(g_zz), np.asarray(g_d),
                               rtol=2e-4, atol=2e-5)


def test_zigzag_ring_masked_matches_dense():
    """Key-masked zigzag (padded / packed-document causal batch) must
    equal dense causal+mask — the balanced schedule is not given up
    when the batch carries padding (VERDICT r3 #5)."""
    from deeplearning4j_tpu.parallel import (
        zigzag_permute, zigzag_ring_self_attention, zigzag_unpermute)
    mesh = make_mesh({"seq": 8})
    n, (b, t, h, d) = 8, (2, 64, 2, 8)
    q = jax.random.normal(jax.random.PRNGKey(12), (b, t, h, d))
    mask = (jnp.arange(t)[None, :]
            < jnp.asarray([[64], [41]])).astype(jnp.float32)
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    want = scaled_dot_attention(q, q, q, mask=mask, causal=True)
    zz = zigzag_ring_self_attention(
        zigzag_permute(q, n), zigzag_permute(q, n),
        zigzag_permute(q, n), mesh,
        mask=zigzag_permute(mask, n, axis=1))
    got = zigzag_unpermute(zz, n)
    valid = np.asarray(mask)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(want) * valid,
                               np.asarray(got) * valid,
                               rtol=2e-4, atol=1e-5)


def test_zigzag_ring_masked_gradients_match():
    from deeplearning4j_tpu.parallel import (
        zigzag_permute, zigzag_ring_self_attention, zigzag_unpermute)
    mesh = make_mesh({"seq": 8})
    n, (b, t, h, d) = 8, (1, 32, 2, 8)
    q = jax.random.normal(jax.random.PRNGKey(13), (b, t, h, d))
    co = jax.random.normal(jax.random.PRNGKey(14), (b, t, h, d))
    mask = (jnp.arange(t)[None, :] < 23).astype(jnp.float32)
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    valid = mask[:, :, None, None]

    def loss_zz(x):
        xz = zigzag_permute(x, n)
        o = zigzag_ring_self_attention(
            xz, xz, xz, mesh, mask=zigzag_permute(mask, n, axis=1))
        return jnp.sum(zigzag_unpermute(o, n) * co * valid)

    def loss_dense(x):
        return jnp.sum(
            scaled_dot_attention(x, x, x, mask=mask, causal=True)
            * co * valid)

    g_zz = jax.grad(loss_zz)(q)
    g_d = jax.grad(loss_dense)(q)
    np.testing.assert_allclose(np.asarray(g_zz), np.asarray(g_d),
                               rtol=2e-4, atol=2e-5)


def test_zigzag_permute_roundtrip():
    from deeplearning4j_tpu.parallel import (zigzag_permute,
                                             zigzag_unpermute)
    x = jnp.arange(2 * 48.0).reshape(2, 48)
    rt = zigzag_unpermute(zigzag_permute(x, 8, axis=1), 8, axis=1)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(x))


@pytest.mark.parametrize("mode", ["ring", "ulysses", "zigzag_ring"])
def test_sequence_parallel_layer_api(mode):
    """MultiHeadAttention(sequence_parallel=...) under an ambient
    distributed_context must equal the same layer outside the context
    (the high-level long-context path; users never touch shard_map)."""
    from deeplearning4j_tpu.parallel import (distributed_context,
                                             make_mesh)
    from deeplearning4j_tpu.nn.layers import MultiHeadAttention
    mesh = make_mesh({"seq": 8})
    t = 32
    layer = MultiHeadAttention(n_in=16, n_out=16, n_heads=8,
                               causal=True, sequence_parallel=mode)
    params, _, _ = layer.init(jax.random.PRNGKey(0), (t, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, 16))
    local, _ = layer.apply(params, {}, x)          # no ambient context
    with distributed_context(mesh):
        dist, _ = layer.apply(params, {}, x)
    np.testing.assert_allclose(np.asarray(local), np.asarray(dist),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("mode", ["ring", "zigzag_ring"])
def test_sequence_parallel_layer_api_masked(mode):
    """Padded batches through the layer API: the key mask reaches the
    distributed attention (zigzag included — VERDICT r3 #5) and the
    result matches local masked attention on valid positions."""
    from deeplearning4j_tpu.parallel import (distributed_context,
                                             make_mesh)
    from deeplearning4j_tpu.nn.layers import MultiHeadAttention
    mesh = make_mesh({"seq": 8})
    t = 32
    layer = MultiHeadAttention(n_in=16, n_out=16, n_heads=8,
                               causal=True, sequence_parallel=mode)
    params, _, _ = layer.init(jax.random.PRNGKey(0), (t, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, t, 16))
    mask = (jnp.arange(t)[None, :]
            < jnp.asarray([[t], [21]])).astype(jnp.float32)
    local, _ = layer.apply(params, {}, x, mask=mask)
    with distributed_context(mesh):
        dist, _ = layer.apply(params, {}, x, mask=mask)
    valid = np.asarray(mask)[:, :, None]
    np.testing.assert_allclose(np.asarray(local) * valid,
                               np.asarray(dist) * valid,
                               rtol=2e-4, atol=2e-5)


def test_sequence_parallel_context_invalidates_traces():
    """A net fit OUTSIDE the context first must re-trace when entering
    it (and vice versa) — the ambient decision is never baked into a
    stale jit cache. Also: a typo'd mode raises even single-chip."""
    from deeplearning4j_tpu.parallel import (distributed_context,
                                             make_mesh)
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import (GlobalPoolingLayer,
                                              MultiHeadAttention,
                                              OutputLayer,
                                              TransformerEncoderBlock)
    from deeplearning4j_tpu.nn import updaters as upd
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(upd.Adam(learning_rate=0.01)).list()
            .layer(TransformerEncoderBlock(n_heads=8, causal=True,
                                           sequence_parallel="ring"))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType("rnn", (16, 16))).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16, 16)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    net.fit(x, y)                      # traces LOCAL attention
    local_fn = net._train_step_fn
    with distributed_context(make_mesh({"seq": 8})):
        net.fit(x, y)                  # must re-trace distributed
        assert net._train_step_fn is not local_fn
        dist_fn = net._train_step_fn
    net.fit(x, y)                      # back outside: re-trace again
    assert net._train_step_fn is not dist_fn
    assert np.isfinite(net.score())

    bad = MultiHeadAttention(n_in=16, n_out=16, n_heads=2,
                             sequence_parallel="ulyses")
    params, _, _ = bad.init(jax.random.PRNGKey(0), (8, 16))
    with pytest.raises(ValueError, match="sequence_parallel"):
        bad.apply(params, {}, jnp.zeros((1, 8, 16)))


def test_sequence_parallel_transformer_trains():
    """A full MultiLayerNetwork with a sequence-parallel transformer
    block trains under the ambient context (grads flow through the
    ring inside the jitted train step)."""
    from deeplearning4j_tpu.parallel import (distributed_context,
                                             make_mesh)
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import (GlobalPoolingLayer,
                                              OutputLayer,
                                              TransformerEncoderBlock)
    from deeplearning4j_tpu.nn import updaters as upd
    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(upd.Adam(learning_rate=0.01)).list()
            .layer(TransformerEncoderBlock(n_heads=8, causal=True,
                                           sequence_parallel="ring"))
            .layer(GlobalPoolingLayer(pooling_type="avg"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType("rnn", (16, 16))).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 16, 16)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    with distributed_context(make_mesh({"seq": 8})):
        for _ in range(3):
            net.fit(x, y)
    assert np.isfinite(net.score())


def test_ulysses_attention_legacy_alias():
    """The original ring_attention.ulysses_attention import location
    must keep working (now delegating to parallel/ulysses.py)."""
    from deeplearning4j_tpu.parallel import ulysses_self_attention
    assert ulysses_attention is ulysses_self_attention
    mesh = make_mesh({"seq": 8})
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (2, 32, 8, 4))
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    np.testing.assert_allclose(
        np.asarray(scaled_dot_attention(q, q, q)),
        np.asarray(ulysses_attention(q, q, q, mesh)),
        rtol=2e-4, atol=2e-5)


def test_parallel_inference_batched():
    net = _net()
    pi = ParallelInference(net, mode=ParallelInference.BATCHED,
                           batch_limit=16)
    try:
        xs = [np.random.default_rng(i).normal(size=(4,)).astype(
            np.float32) for i in range(10)]
        obs = [pi.output_async(x) for x in xs]
        outs = [o.get(timeout=30) for o in obs]
        direct = np.asarray(net.output(np.stack(xs)))
        np.testing.assert_allclose(np.stack(outs), direct, rtol=1e-4,
                                   atol=1e-5)
    finally:
        pi.shutdown()


def test_parallel_inference_error_propagates():
    net = _net()
    pi = ParallelInference(net, mode=ParallelInference.BATCHED)
    try:
        with pytest.raises(Exception):
            pi.output(np.ones((3,), np.float32))  # wrong feature size
    finally:
        pi.shutdown()


def test_tensor_parallel_matmul_sharding():
    """TP capability (new vs reference, SURVEY §2.5): shard a weight's
    output dim over 'model'; XLA partitions the matmul."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh({"data": 2, "model": 4})
    w = jax.device_put(jnp.ones((16, 32)),
                       NamedSharding(mesh, P(None, "model")))
    x = jax.device_put(jnp.ones((8, 16)),
                       NamedSharding(mesh, P("data", None)))
    y = jax.jit(lambda a, b: a @ b)(x, w)
    assert y.shape == (8, 32)
    np.testing.assert_allclose(np.asarray(y), 16.0)


def test_ulysses_attention_matches_full():
    """All-to-all sequence parallelism (second long-context strategy):
    identical outputs to single-device attention, with and without
    mask/causal."""
    from deeplearning4j_tpu.parallel import ulysses_self_attention
    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention

    mesh = make_mesh({"seq": 8})
    b, t, h, d = 2, 32, 8, 4
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, h, d))
    v = jax.random.normal(kv, (b, t, h, d))
    full = scaled_dot_attention(q, k, v)
    uly = ulysses_self_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(full), np.asarray(uly),
                               rtol=2e-4, atol=2e-5)
    # causal
    fullc = scaled_dot_attention(q, k, v, causal=True)
    ulyc = ulysses_self_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(fullc), np.asarray(ulyc),
                               rtol=2e-4, atol=2e-5)
    # key mask
    mask = (np.arange(t)[None, :] < np.array([[20], [28]])).astype(
        np.float32) * np.ones((b, 1), np.float32)
    mask = jnp.asarray(mask)
    fullm = scaled_dot_attention(q, k, v, mask=mask)
    ulym = ulysses_self_attention(q, k, v, mesh, mask=mask)
    np.testing.assert_allclose(np.asarray(fullm), np.asarray(ulym),
                               rtol=2e-4, atol=2e-5)
    # gradient flows through the all-to-alls
    g = jax.grad(lambda q: jnp.sum(
        ulysses_self_attention(q, k, v, mesh) ** 2))(q)
    assert g.shape == q.shape and bool(jnp.all(jnp.isfinite(g)))


def test_ulysses_rejects_indivisible_heads():
    from deeplearning4j_tpu.parallel import ulysses_self_attention
    mesh = make_mesh({"seq": 8})
    x = jnp.zeros((1, 16, 4, 8))    # 4 heads < 8 devices
    with pytest.raises(ValueError, match="divisible"):
        ulysses_self_attention(x, x, x, mesh)

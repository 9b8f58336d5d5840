"""Orbax-backed sharded checkpointing on the 8-device CPU mesh:
save/restore of a TP-sharded pytree preserves values AND shardings;
keep-last-K; resume into a live network; elastic resharded restore
(a ZeRO checkpoint written at N devices restored onto M≠N — the
forced-8-CPU-device reshard fence of ISSUE 7). (SURVEY §5
checkpoint/resume — the scale path next to the zip ModelSerializer.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.serialization import ShardedCheckpointer

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def test_sharded_roundtrip_preserves_sharding(tmp_path):
    mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    sh = NamedSharding(mesh, P(None, "model"))
    w = jax.device_put(
        jnp.arange(16 * 8, dtype=jnp.float32).reshape(16, 8), sh)
    tree = {"params": {"w": w, "b": jnp.ones((8,))},
            "opt_state": {"m": jnp.zeros((16, 8))},
            "state": {}, "meta": {"iteration": 7, "epoch": 1}}
    with ShardedCheckpointer(tmp_path / "ckpt", async_save=False) as ck:
        ck.save(0, tree=tree, wait=True)
        target = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding)
            if hasattr(a, "sharding") else a, tree)
        got = ck.restore(0, target=target)
    np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                  np.asarray(w))
    assert got["params"]["w"].sharding.is_equivalent_to(sh, 2)
    assert int(np.asarray(got["meta"]["iteration"])) == 7


def test_keep_last_k(tmp_path):
    tree = {"x": jnp.ones((4,))}
    with ShardedCheckpointer(tmp_path / "ck", keep_last=2,
                             async_save=False) as ck:
        for s in range(5):
            ck.save(s, tree=tree, wait=True)
        assert ck.all_steps() == [3, 4]
        assert ck.latest_step() == 4


def test_resume_into_network(tmp_path):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd

    def make():
        conf = (NeuralNetConfiguration.builder().seed(3)
                .updater(upd.Adam(learning_rate=0.05)).list()
                .layer(DenseLayer(n_out=8, activation="tanh"))
                .layer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(4)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
    a = make()
    for _ in range(5):
        a.fit(x, y)
    with ShardedCheckpointer(tmp_path / "net", async_save=False) as ck:
        ck.save(a.iteration, a, wait=True)
        b = ck.restore(net=make())
    assert b.iteration == a.iteration
    np.testing.assert_allclose(np.asarray(b.output(x)),
                               np.asarray(a.output(x)), rtol=1e-6)
    # training continues identically from the restored state
    a.fit(x, y)
    b.fit(x, y)
    np.testing.assert_allclose(np.asarray(b.output(x)),
                               np.asarray(a.output(x)), rtol=1e-5)


def test_sharded_checkpoint_listener(tmp_path):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.train.listeners import CheckpointListener

    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(upd.Sgd(learning_rate=0.1)).list()
            .layer(DenseLayer(n_out=4, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    lst = CheckpointListener(tmp_path / "sh", save_every_n_iterations=2,
                             keep_last=2, sharded=True)
    net.listeners.append(lst)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
    for _ in range(6):
        net.fit(x, y)
    lst._ck.wait_until_finished()
    assert lst._ck.all_steps() == [4, 6]
    restored = lst._ck.restore(6, net=MultiLayerNetwork(conf).init())
    np.testing.assert_allclose(np.asarray(restored.output(x)),
                               np.asarray(net.output(x)), rtol=1e-6)


# =========================================================================
# elastic resharded restore (ISSUE 7): save at N, restore at M != N
# =========================================================================

def _zero_wrapper(n, seed=3, feats=6, classes=3, hidden=13):
    """A sharded-update wrapper over the first n of the 8 forced CPU
    devices; hidden=13 makes most flat leaves pad differently under
    8 vs 4 shards (the repad path is actually exercised)."""
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Adam(learning_rate=5e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="tanh"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(feats)).build())
    net = MultiLayerNetwork(conf).init()
    return net, ParallelWrapper(net, workers=n, sharded_update=True,
                                prefetch_buffer=0)


def _fit_steps(wrapper, steps=4, batch=16, feats=6, classes=3, seed=0):
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    rng = np.random.RandomState(seed)
    x = rng.randn(batch * steps, feats).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[
        rng.randint(0, classes, batch * steps)]
    wrapper.fit(ListDataSetIterator(DataSet(x, y), batch_size=batch),
                epochs=1)


def _host_flat_opt(wrapper):
    """The wrapper's live optimizer state as full host-side flat
    leaves (np.asarray of a P('data') global array materializes the
    whole leaf)."""
    return [np.asarray(l)
            for l in jax.tree_util.tree_leaves(wrapper._dp_state)]


def test_reshard_fence_8_to_4_and_back(tmp_path):
    """Acceptance fence: opt/param state saved at N=8 restores onto
    M=4 (and 4→8) with the gathered flat leaves bit-identical to the
    source checkpoint, in this forced-8-CPU-device process."""
    from deeplearning4j_tpu.parallel.zero import repad_flat_leaves
    net8, w8 = _zero_wrapper(8)
    _fit_steps(w8)
    src_flat = _host_flat_opt(w8)
    src_params = [np.asarray(l)
                  for l in jax.tree_util.tree_leaves(net8.params)]
    with ShardedCheckpointer(tmp_path / "ck", async_save=False) as ck:
        ck.save_wrapper(net8.iteration, w8, wait=True)
        assert ck.world_manifest(net8.iteration)["n_shards"] == 8

        # N=8 -> M=4
        net4, w4 = _zero_wrapper(4)
        ck.restore_wrapper(w4)
        assert net4.iteration == net8.iteration
        assert net4.epoch == net8.epoch
        for a, b in zip(jax.tree_util.tree_leaves(net4.params),
                        src_params):
            assert np.array_equal(np.asarray(a), b)
        # gather M=4 shards, re-pad onto the source layout: bit-equal
        flat4 = _host_flat_opt(w4)
        back = repad_flat_leaves(flat4, src_flat)
        for a, b in zip(back, src_flat):
            assert a.dtype == b.dtype and np.array_equal(a, b)

        # M=4 -> N=8 (continue training at 4, save, restore at 8)
        _fit_steps(w4, seed=1)
        ck.save_wrapper(net4.iteration, w4, wait=True)
        assert ck.world_manifest(net4.iteration)["n_shards"] == 4
        src4_flat = _host_flat_opt(w4)
        net8b, w8b = _zero_wrapper(8)
        ck.restore_wrapper(w8b, step=net4.iteration)
        assert net8b.iteration == net4.iteration
        flat8b = _host_flat_opt(w8b)
        back4 = repad_flat_leaves(flat8b, src4_flat)
        for a, b in zip(back4, src4_flat):
            assert np.array_equal(a, b)
        # and the resharded state actually trains (shards are live,
        # not just storage): one more step must not diverge from the
        # same step taken at the source scale... world size differs,
        # so just assert it steps cleanly and stays finite
        _fit_steps(w8b, steps=1, seed=2)
        assert np.isfinite(net8b.score_)


def test_same_topology_restore_stays_fast_path(tmp_path):
    """n_src == wrapper.n keeps the sharded-target restore (shards
    land on their devices; nothing gathers): the restored opt leaves
    carry P('data') shardings."""
    net8, w8 = _zero_wrapper(8)
    _fit_steps(w8)
    with ShardedCheckpointer(tmp_path / "ck", async_save=False) as ck:
        ck.save_wrapper(net8.iteration, w8, wait=True)
        net8b, w8b = _zero_wrapper(8)
        ck.restore_wrapper(w8b)
    from deeplearning4j_tpu.parallel.zero import sharded_leaf
    for leaf in jax.tree_util.tree_leaves(w8b._dp_state):
        if sharded_leaf(leaf, 8):
            assert len(leaf.sharding.device_set) == 8
    for a, b in zip(_host_flat_opt(w8b), _host_flat_opt(w8)):
        assert np.array_equal(a, b)


def test_reshard_refused_without_opt_in(tmp_path):
    net8, w8 = _zero_wrapper(8)
    _fit_steps(w8)
    with ShardedCheckpointer(tmp_path / "ck", async_save=False) as ck:
        ck.save_wrapper(net8.iteration, w8, wait=True)
        _, w4 = _zero_wrapper(4)
        with pytest.raises(ValueError, match="reshard"):
            ck.restore_wrapper(w4, reshard=False)


def test_layout_mismatch_fails_fast_without_quarantine(tmp_path):
    """Restoring a checkpoint dir written by a DIFFERENT net is a
    configuration error: the strict zero-pad invariant raises
    LayoutMismatch and restore_latest_valid must NOT walk the chain
    quarantining every (valid) step."""
    from deeplearning4j_tpu.parallel.zero import LayoutMismatch
    net8, w8 = _zero_wrapper(8, hidden=13)
    _fit_steps(w8)
    with ShardedCheckpointer(tmp_path / "ck", async_save=False) as ck:
        ck.save_wrapper(net8.iteration, w8, wait=True)
        # same leaf COUNT, different layer width -> flat sizes clash
        _, w4 = _zero_wrapper(4, hidden=9)
        with pytest.raises(LayoutMismatch):
            ck.restore_latest_valid(wrapper=w4)
        assert ck.all_steps() == [net8.iteration]   # nothing moved
        assert not (tmp_path / "ck" / "corrupt").exists()


def test_restore_degradation_order_quarantines_then_reshards(tmp_path):
    """Satellite: newest checkpoint written at N=8 is CORRUPT →
    restore_latest_valid onto M=4 quarantines it (with its world
    manifest) and the next-newest valid step still reshards."""
    from deeplearning4j_tpu.obs import metrics
    net8, w8 = _zero_wrapper(8)
    _fit_steps(w8)
    good_step = net8.iteration
    good_params = [np.asarray(l)
                   for l in jax.tree_util.tree_leaves(net8.params)]
    ck = ShardedCheckpointer(tmp_path / "ck", keep_last=5,
                             async_save=False)
    ck.save_wrapper(good_step, w8, wait=True)
    _fit_steps(w8, seed=1)
    bad_step = net8.iteration
    ck.save_wrapper(bad_step, w8, wait=True)
    # rot the newest step dir (truncate every tensorstore file)
    for f in (tmp_path / "ck" / str(bad_step)).rglob("*"):
        if f.is_file():
            f.write_bytes(f.read_bytes()[:3])
    q0 = metrics.CKPT_QUARANTINED._children[()].get()
    net4, w4 = _zero_wrapper(4)
    ck.restore_latest_valid(wrapper=w4)
    assert net4.iteration == good_step      # fell back, resharded
    for a, b in zip(jax.tree_util.tree_leaves(net4.params),
                    good_params):
        assert np.array_equal(np.asarray(a), b)
    assert metrics.CKPT_QUARANTINED._children[()].get() == q0 + 1
    assert (tmp_path / "ck" / "corrupt" / str(bad_step)).exists()
    # the corrupt step's world manifest moved with it
    assert not (tmp_path / "ck" / f"world_{bad_step}.json").exists()
    assert (tmp_path / "ck" / "corrupt"
            / f"world_{bad_step}.json").exists()
    assert ck.all_steps() == [good_step]
    ck.close()


def test_listener_iter_and_epoch_saves_no_step_collision(tmp_path):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.train.listeners import CheckpointListener

    conf = (NeuralNetConfiguration.builder().seed(3)
            .updater(upd.Sgd(learning_rate=0.1)).list()
            .layer(DenseLayer(n_out=4, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    # every 2 iters AND every epoch; 4 batches/epoch → epoch-end save
    # lands on an iteration already saved (would collide without dedup)
    lst = CheckpointListener(tmp_path / "both",
                             save_every_n_iterations=2,
                             save_every_n_epochs=1, keep_last=10,
                             sharded=True)
    net.listeners.append(lst)
    rng = np.random.default_rng(0)
    data = [DataSet(rng.standard_normal((8, 4)).astype(np.float32),
                    np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)])
            for _ in range(4)]
    net.fit(ListDataSetIterator(data), epochs=2)   # no crash = no collision
    lst.flush()
    assert lst._ck.all_steps() == [2, 4, 6, 8]

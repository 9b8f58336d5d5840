"""pjit parameter-sharded serving (SURVEY §2.5 model-parallel
inference row): a model sharded over the mesh 'model' axis must hold
~1/N of its parameter bytes per device and produce outputs identical
to the unsharded network."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.parallel import (ParallelInference, make_mesh,
                                         shard_model_params)


def _wide_net(hidden=512, n_in=64, classes=8):
    conf = (NeuralNetConfiguration.builder().seed(11)
            .updater(upd.Sgd(learning_rate=1e-2)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=classes, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _param_bytes(tree):
    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(tree))


def _local_bytes(tree):
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shard = leaf.addressable_shards[0]
        total += shard.data.size * shard.data.dtype.itemsize
    return total


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_sharded_params_bytes_and_outputs_match():
    net = _wide_net()
    x = np.random.default_rng(0).normal(size=(16, 64)).astype(np.float32)
    want = np.asarray(net.output(x))
    total = _param_bytes(net.params)

    mesh = make_mesh({"model": 8})
    shard_model_params(net, mesh, "model")

    # big weights sharded 8-ways: local bytes well under the total
    # (biases and the small head replicate)
    local = _local_bytes(net.params)
    assert local < total / 4, (local, total)
    # the dominant hidden x hidden weight must be exactly 1/8 local
    w2 = net.params["layer_1"]["W"]
    assert w2.addressable_shards[0].data.size * 8 == w2.size

    got = np.asarray(net.output(x))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_parallel_inference_sharded_serving():
    net = _wide_net()
    x = np.random.default_rng(1).normal(size=(4, 64)).astype(np.float32)
    want = np.asarray(net.output(x))
    mesh = make_mesh({"model": 8})
    pi = ParallelInference(net, mode=ParallelInference.BATCHED,
                           mesh=mesh, shard_params=True)
    try:
        got = pi.output(x)
    finally:
        pi.shutdown()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_shard_params_requires_mesh():
    net = _wide_net(hidden=32)
    with pytest.raises(ValueError, match="needs a mesh"):
        ParallelInference(net, shard_params=True)

"""Model-parallel serving: a network whose parameters exceed one
chip's HBM served across a mesh with per-layer NamedSharding
(SURVEY §2.5 "shard large models with pjit"; the reference's
ParallelInference is replica-only).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/sharded_serving.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in \
        os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += \
        " --xla_force_host_platform_device_count=8"

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import jax

    import numpy as np

    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.parallel import (ParallelInference,
                                             make_mesh)

    hidden = 256 if FAST else 2048
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(upd.Sgd(learning_rate=1e-2)).list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(OutputLayer(n_out=16, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(64)).build())
    net = MultiLayerNetwork(conf).init()
    total = sum(l.size * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(net.params))

    n = min(8, len(jax.devices()))
    mesh = make_mesh({"model": n})
    pi = ParallelInference(net, mesh=mesh, shard_params=True)
    local = sum(l.addressable_shards[0].data.size
                * l.addressable_shards[0].data.dtype.itemsize
                for l in jax.tree_util.tree_leaves(net.params))
    print(f"params {total/1e6:.1f} MB total -> {local/1e6:.1f} MB "
          f"per device over {n} devices")

    x = np.random.default_rng(0).normal(size=(4, 64)).astype(np.float32)
    try:
        out = pi.output(x)
    finally:
        pi.shutdown()
    print(f"served batch through the sharded mesh: probs sum "
          f"{out.sum(1).round(3)}")


if __name__ == "__main__":
    main()

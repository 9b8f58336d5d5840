"""LeNet on MNIST — the reference's LeNetMnistExample
(dl4j-examples): config builder -> fit -> Evaluation -> save/load.
Runs on CPU or TPU; uses the synthetic MNIST fallback without data.

    python examples/lenet_mnist.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import numpy as np
    from deeplearning4j_tpu.data.mnist import MnistDataSetIterator
    from deeplearning4j_tpu.serialization import ModelSerializer
    from deeplearning4j_tpu.zoo import LeNet

    n_train = 1024 if FAST else 16384
    train_it = MnistDataSetIterator(batch_size=64, train=True,
                                    n_examples=n_train)
    test_it = MnistDataSetIterator(batch_size=256, train=False,
                                   n_examples=n_train // 4)

    net = LeNet(num_classes=10, seed=123).init()
    print(f"LeNet: {net.num_params():,} params "
          f"(synthetic MNIST: {train_it.synthetic})")
    net.fit(train_it, epochs=1 if FAST else 3, steps_per_loop=4)
    ev = net.evaluate(test_it)
    print(ev.stats())

    import tempfile
    path = os.path.join(tempfile.mkdtemp(), "lenet_example.zip")
    ModelSerializer.write_model(net, path)
    net2 = ModelSerializer.restore_multi_layer_network(path)
    x = next(iter(test_it)).features[:4]
    assert np.allclose(np.asarray(net.output(x)),
                       np.asarray(net2.output(x)))
    print(f"saved + restored OK -> {path}")


if __name__ == "__main__":
    main()

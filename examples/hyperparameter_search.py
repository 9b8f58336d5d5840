"""Hyperparameter search with the Arbiter analog — random search over
learning rate / width / updater for a classifier (reference:
arbiter's OptimizationRunner + ParameterSpace over a
MultiLayerConfiguration, SURVEY §2 arbiter row).

    python examples/hyperparameter_search.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import numpy as np
    from deeplearning4j_tpu.arbiter import (
        ContinuousParameterSpace, DiscreteParameterSpace,
        IntegerParameterSpace, OptimizationRunner, RandomSearchGenerator)
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd

    rng = np.random.RandomState(0)
    x = rng.randn(256, 10).astype(np.float32)
    w_true = rng.randn(10, 3)
    y = np.eye(3, dtype=np.float32)[np.argmax(x @ w_true, axis=1)]
    train, test = DataSet(x[:192], y[:192]), DataSet(x[192:], y[192:])

    space = {
        "lr": ContinuousParameterSpace(1e-4, 1e-1, log=True),
        "hidden": IntegerParameterSpace(8, 64),
        "updater": DiscreteParameterSpace(["adam", "rmsprop"]),
    }

    def build_and_score(cand):
        u = (upd.Adam(learning_rate=cand["lr"])
             if cand["updater"] == "adam"
             else upd.RmsProp(learning_rate=cand["lr"]))
        conf = (NeuralNetConfiguration.builder().seed(7).updater(u)
                .list()
                .layer(DenseLayer(n_out=cand["hidden"],
                                  activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(10)).build())
        net = MultiLayerNetwork(conf).init()
        net.fit(ListDataSetIterator([train], batch_size=192),
                epochs=5 if FAST else 40)
        return net.score(test), net

    runner = OptimizationRunner(
        RandomSearchGenerator(space, seed=1),
        build_and_score,
        max_candidates=3 if FAST else 12)
    best = runner.execute()
    print(f"evaluated {len(runner.results)} candidates")
    for r in sorted(runner.results, key=lambda r: r.score)[:3]:
        print(f"  score {r.score:.4f}  <- {r.params}")
    print(f"best: {best.params} (test loss {best.score:.4f})")


if __name__ == "__main__":
    main()

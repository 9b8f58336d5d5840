"""Transfer learning — freeze a pretrained feature extractor, replace
the head, fine-tune on a new task (reference:
TransferLearning.Builder + FineTuneConfiguration +
TransferLearningHelper featurization, SURVEY §2.3).

    python examples/transfer_learning.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.nn.transferlearning import (
        FineTuneConfiguration, TransferLearning, TransferLearningHelper)

    rng = np.random.RandomState(0)
    epochs = 4 if FAST else 30

    # --- 1. "pretrain" a base model on task A (4-way) ------------------
    xa = rng.randn(256, 12).astype(np.float32)
    wa = rng.randn(12, 4)
    ya = np.eye(4, dtype=np.float32)[np.argmax(xa @ wa, axis=1)]
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(upd.Adam(learning_rate=5e-3)).list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(12)).build())
    base = MultiLayerNetwork(conf).init()
    base.fit(ListDataSetIterator([DataSet(xa, ya)], batch_size=256),
             epochs=epochs)
    print(f"base model task-A loss: {base.score(DataSet(xa, ya)):.4f}")

    # --- 2. freeze features, new 2-way head, fine-tune on task B --------
    xb = rng.randn(128, 12).astype(np.float32)
    yb = np.eye(2, dtype=np.float32)[(xb @ wa[:, 0] > 0).astype(int)]
    ft = (TransferLearning.builder(base)
          .fine_tune_configuration(FineTuneConfiguration(
              updater=upd.Adam(learning_rate=1e-3)))
          .set_feature_extractor(1)           # freeze layers 0..1
          .remove_output_layer()
          .add_layer(OutputLayer(n_out=2, activation="softmax",
                                 loss="mcxent"))
          .build())
    # snapshot to host BEFORE fit: the jitted step donates param buffers
    frozen_before = np.asarray(ft.params["layer_0"]["W"]).copy()
    ft.fit(ListDataSetIterator([DataSet(xb, yb)], batch_size=128),
           epochs=epochs)
    drift = float(np.abs(np.asarray(ft.params["layer_0"]["W"])
                         - frozen_before).max())
    print(f"fine-tuned task-B loss: {ft.score(DataSet(xb, yb)):.4f} "
          f"(frozen-layer drift: {drift:.2e})")

    # --- 3. featurization path (TransferLearningHelper) ----------------
    helper = TransferLearningHelper(base, frozen_until=1)
    feats = helper.featurize(DataSet(xb, yb))
    print(f"featurized activations: {np.asarray(feats.features).shape} "
          "(train a head on these without re-running the frozen trunk)")


if __name__ == "__main__":
    main()

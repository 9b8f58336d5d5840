"""Pretrained model zoo — restore checksum-verified weights, predict,
fine-tune, and publish your own (reference: ZooModel.initPretrained +
DL4JResources; dl4j-examples' pretrained VGG16 flow).

    python examples/pretrained_zoo.py
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import numpy as np
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.zoo import LeNet, export_pretrained

    # 1. restore the checked-in pretrained weights (sha256-verified)
    assert LeNet.pretrained_available()
    net = LeNet.init_pretrained()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 14, 14, 1)).astype(np.float32)
    probs = np.asarray(net.output(x))
    print(f"pretrained LeNet: predicted classes {probs.argmax(1)}")

    # 2. fine-tune on new data
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 64)]
    xt = rng.normal(size=(64, 14, 14, 1)).astype(np.float32)
    it = ListDataSetIterator(DataSet(xt, y), batch_size=32)
    for _ in range(1 if FAST else 5):
        net.fit(it)
    print(f"fine-tuned score: {net.score():.3f}")

    # 3. publish to your own weight repository (manifest + checksum)
    with tempfile.TemporaryDirectory() as repo:
        artifact = export_pretrained(net, "LeNet", "mytask", repo)
        print(f"published {artifact.name} "
              f"({artifact.stat().st_size // 1024} kB) with manifest")
        restored = LeNet.init_pretrained("mytask", base_dir=repo)
        assert np.allclose(np.asarray(restored.output(x)),
                           np.asarray(net.output(x)), atol=1e-6)
        print("round-trip restore matches")


if __name__ == "__main__":
    main()

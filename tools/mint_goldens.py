"""Mint the checked-in pretrained goldens under resources/pretrained.

Reference analog: the weights dl4j hosts on dl4jResources; here the
artifacts are *tiny* variants (small input shapes / vocab) trained
briefly on deterministic synthetic tasks, so the repository stays
small while the full export→checksum→restore→forward contract is
exercised.  Each model directory also carries ``golden_io.npz``
(input, expected output) so restores can be verified bit-for-bit
against the forward pass that minted them.

Run: ``python tools/mint_goldens.py`` (idempotent; rewrites goldens).
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax

# CPU-only by nature: the goldens pin the float32 CPU numerics the
# tier-1 tests compare against, on any host (chip attached or not)
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from deeplearning4j_tpu.data import DataSet, ListDataSetIterator  # noqa: E402
from deeplearning4j_tpu.zoo import (CausalTransformerLM,  # noqa: E402
                                    LeNet, SimpleCNN,
                                    TextGenerationLSTM)
from deeplearning4j_tpu.zoo.pretrained import export_pretrained  # noqa: E402

BASE = Path(__file__).resolve().parents[1] / "resources" / "pretrained"


def _train_briefly(net, x, y, epochs=3, batch=16):
    it = ListDataSetIterator(DataSet(x, y), batch_size=batch)
    for _ in range(epochs):
        net.fit(it)
    return net


def _synthetic_images(rng, n, h, w, c, classes):
    y_idx = rng.integers(0, classes, n)
    x = rng.normal(size=(n, h, w, c)).astype(np.float32) * 0.3
    # class-dependent mean so the task is learnable
    x += (y_idx[:, None, None, None] / classes).astype(np.float32)
    return x, np.eye(classes, dtype=np.float32)[y_idx]


def mint(model_cls, net, x, base=BASE, dataset="default"):
    art = export_pretrained(net, model_cls.model_name(), dataset, base)
    out = np.asarray(net.output(x[:4]))
    np.savez_compressed(art.parent / f"{dataset}_golden_io.npz",
                        x=x[:4], y=out)
    print(f"minted {art} ({art.stat().st_size/1e3:.0f} kB), "
          f"golden out mean {out.mean():.4f}")


def main():
    rng = np.random.default_rng(20260730)

    # LeNet on a 14x14 synthetic digit task (tiny flagship variant)
    x, y = _synthetic_images(rng, 128, 14, 14, 1, 10)
    lenet = LeNet(num_classes=10, seed=7, input_shape=(14, 14, 1)).init()
    mint(LeNet, _train_briefly(lenet, x, y), x)

    # SimpleCNN tiny variant (16x16x3, 4 classes) to keep the golden
    # small; the reference default input is 48x48x3
    x, y = _synthetic_images(rng, 64, 16, 16, 3, 4)
    scnn = SimpleCNN(num_classes=4, seed=7, input_shape=(16, 16, 3)).init()
    mint(SimpleCNN, _train_briefly(scnn, x, y), x)

    # TextGenerationLSTM with a tiny vocabulary
    vocab, t, n = 12, 20, 64
    ids = rng.integers(0, vocab, (n, t + 1))
    xs = np.eye(vocab, dtype=np.float32)[ids[:, :-1]]      # [N,T,V]
    ys = np.eye(vocab, dtype=np.float32)[ids[:, 1:]]
    lstm = TextGenerationLSTM(vocab_size=vocab, seed=7, hidden=16,
                              layers=1, tbptt=10).init()
    mint(TextGenerationLSTM, _train_briefly(lstm, xs, ys), xs)

    # CausalTransformerLM nano variant (decoder-only LM family)
    model = CausalTransformerLM(vocab_size=16, hidden=32, n_layers=2,
                                n_heads=4, n_kv_heads=2, max_len=32,
                                seed=7)
    net = model.init(seq_len=12)
    tokens = np.arange(13) % 5 + 1
    lx = np.tile(tokens[:12], (8, 1)).astype(np.int32)
    ly = np.tile(tokens[1:13], (8, 1)).astype(np.int32)
    mint(CausalTransformerLM,
         _train_briefly(net, lx, ly, epochs=20, batch=8), lx)


if __name__ == "__main__":
    main()

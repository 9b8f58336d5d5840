"""Masked-LM pretraining from raw text: wordpiece vocab → BertIterator
(15% masking, 80/10/10 corruption) → BertTiny MLM head — the upstream
``BertIterator`` UNSUPERVISED-task flow, whole step jitted.

    python examples/bert_pretrain_mlm.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import numpy as np

    from deeplearning4j_tpu.nlp import (BertIterator,
                                        BertWordPieceTokenizer)
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.zoo import BertTiny

    corpus = ["the quick brown fox jumps over the lazy dog",
              "pack my box with five dozen liquor jugs",
              "how vexingly quick daft zebras jump",
              "the five boxing wizards jump quickly",
              "sphinx of black quartz judge my vow"] * 8
    vocab = BertWordPieceTokenizer.build_vocab(corpus)
    tok = BertWordPieceTokenizer(vocab)
    print(f"wordpiece vocab: {len(vocab)} pieces")

    net = BertTiny(vocab_size=len(vocab), max_len=32,
                   updater=upd.Adam(learning_rate=1e-3),
                   seed=11).init_mlm(seq_len=16)
    it = BertIterator(tok, corpus, batch_size=8, seq_len=16,
                      task="mask_lm", seed=1)
    epochs = 2 if FAST else 12
    s0 = None
    for e in range(epochs):
        net.fit(it)
        it.reset()                 # fresh masking every epoch
        s0 = s0 if s0 is not None else net.score()
    print(f"MLM loss {s0:.3f} -> {net.score():.3f} "
          f"after {epochs} epochs (decreasing: {net.score() < s0})")

    # probe: mask one token and ask the model to fill it
    ids, segs, _ = it._encode_fixed("the quick brown fox")
    masked = list(ids)
    pos = 3                        # position of "brown"
    masked[pos] = vocab["[MASK]"]
    probs = np.asarray(net.output(
        np.asarray([masked], np.int32),
        np.asarray([segs], np.int32))[0])
    inv = {i: w for w, i in vocab.items()}
    top = np.argsort(-probs[0, pos])[:3]
    print("fill-in-the-blank 'the quick [MASK] fox' →",
          [inv[int(t)] for t in top])


if __name__ == "__main__":
    main()

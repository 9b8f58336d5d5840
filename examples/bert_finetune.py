"""BERT fine-tune for sequence classification (BASELINE config #4) —
a tiny BERT trained on a synthetic keyword-sentiment task.

    python examples/bert_finetune.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"


def main():
    import numpy as np
    from deeplearning4j_tpu.zoo.bert import Bert
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.eval_.evaluation import Evaluation

    vocab, seq_len, batch = 1000, 32, 32
    GOOD, BAD = 7, 13          # sentiment carrier tokens
    bert = Bert(vocab_size=vocab, hidden=64, n_layers=2, n_heads=4,
                max_len=seq_len, dropout=0.1,
                updater=upd.Adam(learning_rate=1e-3))
    net = bert.init_classifier(num_classes=2, seq_len=seq_len)
    print(f"tiny BERT: {net.num_params():,} params")

    rng = np.random.default_rng(0)

    def make_batch():
        ids = rng.integers(20, vocab, (batch, seq_len))
        labels = rng.integers(0, 2, batch)
        pos = rng.integers(1, seq_len, batch)
        ids[np.arange(batch), pos] = np.where(labels == 1, GOOD, BAD)
        segs = np.zeros((batch, seq_len), np.int32)
        y = np.eye(2, dtype=np.float32)[labels]
        return ids, segs, y

    steps = 20 if FAST else 200
    for i in range(steps):
        ids, segs, y = make_batch()
        net.fit([ids, segs], [y])
        if (i + 1) % max(1, steps // 5) == 0:
            print(f"step {i+1}/{steps}  loss {net.score():.3f}")

    ids, segs, y = make_batch()
    preds = np.asarray(net.output(ids, segs)[0])
    ev = Evaluation(2)
    ev.eval(y, preds)
    print(f"held-out accuracy: {ev.accuracy():.3f}")


if __name__ == "__main__":
    main()

"""Character-level text generation with GravesLSTM + truncated BPTT —
the reference's GravesLSTMCharModellingExample (BASELINE config #3).

    python examples/char_rnn_textgen.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

FAST = os.environ.get("DL4J_TPU_EXAMPLE_FAST") == "1"

CORPUS = (
    "the quick brown fox jumps over the lazy dog. "
    "pack my box with five dozen liquor jugs. "
    "how vexingly quick daft zebras jump! "
    "sphinx of black quartz, judge my vow. "
) * 40


def main():
    import numpy as np
    from deeplearning4j_tpu.zoo.textgen_lstm import TextGenerationLSTM

    chars = sorted(set(CORPUS))
    idx = {c: i for i, c in enumerate(chars)}
    data = np.asarray([idx[c] for c in CORPUS], np.int32)

    seq, batch = 50, 16
    model = TextGenerationLSTM(vocab_size=len(chars),
                               hidden=64 if FAST else 256,
                               layers=2, tbptt=25)
    net = model.init()

    def batches(n):
        rng = np.random.default_rng(0)
        for _ in range(n):
            starts = rng.integers(0, data.size - seq - 1, batch)
            ids = np.stack([data[s:s + seq] for s in starts])
            nxt = np.stack([data[s + 1:s + seq + 1] for s in starts])
            x = np.eye(len(chars), dtype=np.float32)[ids]
            y = np.eye(len(chars), dtype=np.float32)[nxt]
            yield x, y

    steps = 30 if FAST else 300
    for i, (x, y) in enumerate(batches(steps)):
        net.fit(x, y)
        if (i + 1) % max(1, steps // 5) == 0:
            print(f"step {i+1}/{steps}  loss {net.score():.3f}")

    # sample: greedy generation char by char via stored-state stepping
    # (reference rnnTimeStep API — state carried inside the net)
    seed = "the "
    out = list(seed)
    net.rnn_clear_previous_state()
    x = np.eye(len(chars), dtype=np.float32)[[idx[c] for c in seed]][None]
    for _ in range(80):
        y = net.rnn_time_step(x)
        nxt = int(np.asarray(y)[0, -1].argmax())
        out.append(chars[nxt])
        x = np.eye(len(chars), dtype=np.float32)[[nxt]][None]
    print("generated:", "".join(out))


if __name__ == "__main__":
    main()

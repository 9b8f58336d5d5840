"""Decoder-only causal LM: train on a toy corpus, decode with the
KV-cached scan, and (optionally) train sequence-parallel over a mesh —
the modern-LM family the reference lacks (its LM story is char-RNN +
imported BERT).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/causal_lm.py
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

    from deeplearning4j_tpu.zoo import GPTNano

    # toy corpus: learn to continue a repeating token melody
    # (t divisible by the mesh size so the ring-SP section shards)
    period, t = 7, 32
    model = GPTNano(vocab_size=32, max_len=64, seed=11)
    net = model.init(seq_len=t)
    tokens = np.arange(t + 1) % period + 1
    x = np.tile(tokens[:t], (8, 1)).astype(np.int32)
    y = np.tile(tokens[1:t + 1], (8, 1)).astype(np.int32)
    steps = 15 if FAST else 80
    for i in range(steps):
        net.fit(x, y)
    print(f"trained {steps} steps, loss {net.score():.4f}")

    prompt = (np.arange(10) % period + 1)[None, :].astype(np.int32)
    out = model.generate(net, prompt, n_new=10)
    print("prompt       :", prompt[0].tolist())
    print("continuation :", out[0, 10:].tolist())
    want = (np.arange(10, 20) % period + 1).tolist()
    print("expected     :", want,
          "MATCH" if out[0, 10:].tolist() == want else "(still learning)")

    # production serving recipe (round 4): tied embeddings train the
    # GPT-2 way; bf16 + weight-only int8 serving halve-then-halve the
    # per-token HBM traffic — greedy outputs stay identical
    tied = GPTNano(vocab_size=32, max_len=64, seed=11,
                   tie_embeddings=True, compute_dtype="bfloat16")
    tnet = tied.init(seq_len=t)
    for _ in range(steps):
        tnet.fit(x, y)
    full_out = tied.generate(tnet, prompt, n_new=10)
    server = GPTNano(vocab_size=32, max_len=64, seed=11,
                     tie_embeddings=True, compute_dtype="bfloat16",
                     serve_quant="int8")
    q_out = server.generate(tnet, prompt, n_new=10)
    print("int8-served  :", q_out[0, 10:].tolist(),
          "MATCH" if q_out.tolist() == full_out.tolist()
          else "DIVERGED from full precision!")

    # the same config trains sequence-parallel — layer API only
    from deeplearning4j_tpu.parallel import (distributed_context,
                                             make_mesh)
    sp = GPTNano(vocab_size=32, max_len=64, seed=11,
                 sequence_parallel="ring")
    spnet = sp.init(seq_len=t)
    with distributed_context(make_mesh(
            {"seq": min(8, len(jax.devices()))})):
        for _ in range(3 if FAST else 10):
            spnet.fit(x, y)
    print(f"sequence-parallel ring training: loss {spnet.score():.4f}")


if __name__ == "__main__":
    main()

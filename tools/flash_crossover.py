"""Measure the einsum-vs-flash crossover for scaled_dot_attention.

Grounds DL4J_TPU_FLASH_MIN_T (the platform-helper dispatch threshold,
``nn.layers.attention._use_flash``) in measurement instead of
folklore: times one fwd+bwd attention step through BOTH paths at a sweep of
sequence lengths on the attached chip and prints the per-T ratio plus
the smallest T where the kernel wins. One process; needs the TPU.

    python tools/flash_crossover.py [--heads 8] [--dim 64] [--batch 4]

Timing protocol: compile first, then the median of 5 runs of a chain
of REPS dependent steps inside ONE jit (a device-side ``fori_loop``),
each run ended by ``block_until_ready`` — a single attention step is
shorter than one dispatch, so the chain is what makes the host's
share negligible.
"""
from __future__ import annotations

import argparse
import sys
import time

from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lens", type=int, nargs="*",
                    default=[256, 512, 1024, 2048, 4096])
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

    if jax.default_backend() != "tpu":
        sys.exit("flash_crossover needs a TPU: the crossover is a "
                 "device timing")

    def dense(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.asarray(d, q.dtype))
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(s, axis=-1), v)

    # one attention step is shorter than a dispatch: chain REPS
    # dependent steps inside one jit so one dispatch + one barrier is
    # amortised over the whole chain
    REPS = 150

    def timed(fn, x):
        import jax.lax as lax

        grad1 = jax.grad(
            lambda x: jnp.sum(fn(x, x, x).astype(jnp.float32)))

        def chain(n):
            return jax.jit(lambda x: lax.fori_loop(
                0, n, lambda i, xx: grad1(xx).astype(x.dtype), x))

        run = chain(REPS)
        jax.block_until_ready(run(x))             # compile
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[2] / REPS

    key = jax.random.PRNGKey(0)
    print("| T | einsum ms | flash ms | flash/einsum |")
    print("|---|---|---|---|")
    crossover = None
    for t in args.lens:
        x = jax.random.normal(
            key, (args.batch, t, args.heads, args.dim), jnp.bfloat16)
        te = timed(dense, x)
        tf = timed(lambda q, k, v: flash_attention(q, k, v), x)
        # ≥5% win, else it's timing noise; once crossed, stays crossed
        if crossover is None and tf < 0.95 * te:
            crossover = t
        print(f"| {t} | {te * 1e3:.2f} | {tf * 1e3:.2f} "
              f"| {tf / te:.2f} |")
    print(f"# flash wins (>5%) from T={crossover} "
          f"(set DL4J_TPU_FLASH_MIN_T accordingly; masked/long-context "
          f"workloads may prefer it lower — the einsum path "
          f"materialises [T,T] scores)"
          if crossover else "# einsum won at every measured T")


if __name__ == "__main__":
    main()

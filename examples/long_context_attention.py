"""Long-context attention: two sequence-parallel strategies over a
device mesh (beyond-reference capability; the reference's longest-
sequence story is truncated BPTT).

- ring attention: KV blocks rotate around the ICI ring (ppermute),
  O(T/N) memory per device — use for extreme lengths / masks.
- Ulysses: all_to_all trades the sequence axis for the head axis, two
  collectives per call — use when heads >= mesh size.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_attention.py
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

    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.nn.layers.attention import \
        scaled_dot_attention
    from deeplearning4j_tpu.parallel import (make_mesh,
                                             ring_self_attention,
                                             ulysses_self_attention)

    n = min(8, len(jax.devices()))
    mesh = make_mesh({"seq": n})
    b, t, h, d = 2, (8 * n if FAST else 64 * n), 8, 32
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, t, h, d))
    k = jax.random.normal(kk, (b, t, h, d))
    v = jax.random.normal(kv, (b, t, h, d))

    full = scaled_dot_attention(q, k, v)
    ring = ring_self_attention(q, k, v, mesh)
    uly = ulysses_self_attention(q, k, v, mesh)
    err_r = float(jnp.max(jnp.abs(full - ring)))
    err_u = float(jnp.max(jnp.abs(full - uly)))
    print(f"T={t} over {n} devices: ring err {err_r:.2e}, "
          f"ulysses err {err_u:.2e} (both vs single-device attention)")

    # gradients flow through both collective patterns
    g = jax.grad(lambda q: jnp.sum(
        ring_self_attention(q, k, v, mesh) ** 2))(q)
    gu = jax.grad(lambda q: jnp.sum(
        ulysses_self_attention(q, k, v, mesh) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()
    assert np.isfinite(np.asarray(gu)).all()
    print("gradients finite through ppermute ring and all_to_all swap")

    # the flagship workload: CAUSAL-LM training step with sequence-
    # parallel ring attention — per ring step the flash kernel masks
    # above the (globally-offset) diagonal and skips dead blocks
    full_c = scaled_dot_attention(q, k, v, causal=True)
    ring_c = ring_self_attention(q, k, v, mesh, causal=True)
    err_c = float(jnp.max(jnp.abs(full_c - ring_c)))

    import optax
    wq = jax.random.normal(jax.random.PRNGKey(1), (d, d)) * 0.05

    def lm_loss(wq, x):
        qp = jnp.einsum("bthd,de->bthe", x, wq)
        out = ring_self_attention(qp, x, x, mesh, causal=True)
        # next-position prediction surrogate on the sharded axis
        return jnp.mean((out[:, :-1] - x[:, 1:]) ** 2)

    opt = optax.adam(1e-2)
    state = opt.init(wq)
    losses = []
    for _ in range(3):
        loss, grad = jax.value_and_grad(lm_loss)(wq, q)
        upd, state = opt.update(grad, state, wq)
        wq = optax.apply_updates(wq, upd)
        losses.append(float(loss))
    print(f"causal ring err {err_c:.2e}; causal-LM train losses "
          f"{['%.4f' % l for l in losses]} (decreasing: "
          f"{losses[-1] < losses[0]})")


if __name__ == "__main__":
    main()

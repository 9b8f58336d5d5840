"""Plain reference of a dense decoder-only transformer of the Mistral
family (``mistralai/Mistral-7B-v0.3``: pre-norm RMSNorm, rotary
positions in the half-split pairing of the published implementation,
grouped-query attention, SwiGLU, untied head, no sliding window), in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: one sequence, one full
causal forward, no kernels, cache or batching.

Imports nothing of the program. It reads a parameter tree by the zoo's
names (``layer_0.W`` the embedding, ``layer_<i>.mha.Wq``,
``layer_<L+2>.W`` the head), which the benchmark made from the seed.

``precision="fp8"`` is the benchmark's control (see PERF.md): every
matrix product takes operands rounded to float8 e4m3 with a per-tensor
scale. It has to come out as not correct.
"""
import functools

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _operand(x, precision):
    if precision != "fp8":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(x.dtype) * scale


def _mm(a, b, precision):
    return _operand(a, precision) @ _operand(b, precision)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def rope(x, theta):
    """x [T, H, D]: feature i turns with feature i + D/2."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(p, x, *, n_heads, n_kv_heads, head_dim, rope_theta, eps,
          precision):
    t = x.shape[0]
    h = rms_norm(x, p["ln1"]["gamma"], eps)
    a = p["mha"]
    q = rope(_mm(h, a["Wq"], precision).reshape(t, n_heads, head_dim),
             rope_theta)
    k = rope(_mm(h, a["Wk"], precision).reshape(t, n_kv_heads, head_dim),
             rope_theta)
    v = _mm(h, a["Wv"], precision).reshape(t, n_kv_heads, head_dim)
    groups = n_heads // n_kv_heads
    k = jnp.repeat(k, groups, axis=1)       # query head h reads kv h//g
    v = jnp.repeat(v, groups, axis=1)
    s = jnp.einsum("qhd,khd->hqk", _operand(q, precision),
                   _operand(k, precision)) / jnp.sqrt(
                       jnp.float32(head_dim))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", _operand(w, precision),
                   _operand(v, precision)).reshape(t, -1)
    x = x + _mm(o, a["Wo"], precision) + a["bo"]
    h = rms_norm(x, p["ln2"]["gamma"], eps)
    h = jax.nn.silu(_mm(h, p["Wg"], precision)) * _mm(h, p["Wu"],
                                                      precision)
    return x + _mm(h, p["Wd"], precision)


@functools.partial(jax.jit, static_argnames=(
    "n_layers", "n_heads", "n_kv_heads", "head_dim", "rope_theta", "eps",
    "rows", "precision"))
def logits_from(params, tokens, start, *, n_layers, n_heads, n_kv_heads,
                head_dim, rope_theta, eps, rows, precision="float32"):
    """Next-token logits [rows, V] at positions ``start .. start+rows-1``
    of one sequence ``tokens`` [T], after a full causal forward."""
    x = params["layer_0"]["W"][tokens]
    for i in range(n_layers):
        x = block(params[f"layer_{i + 1}"], x, n_heads=n_heads,
                  n_kv_heads=n_kv_heads, head_dim=head_dim,
                  rope_theta=rope_theta, eps=eps, precision=precision)
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = rms_norm(x, params[f"layer_{n_layers + 1}"]["gamma"], eps)
    head = params[f"layer_{n_layers + 2}"]
    return _mm(x, head["W"], precision) + head["b"]


def dims(config: dict) -> dict:
    """The reference's sizes, from the published configuration keys."""
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or (
            config["hidden_size"] // config["num_attention_heads"]),
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]))


def served_gaps(params, config, seq, t0, n_out, *, pad_to, rows,
                control=False):
    """How far below the reference's best logit each served token lies.

    ``seq`` is one request's prompt (``t0`` tokens) followed by its
    ``n_out`` served tokens. The reference runs once over it, teacher
    forced; position ``t0 - 1 + j`` predicts served token ``j``. Returns
    the gaps [n_out] in the reference's float32 logits. With
    ``control`` the token judged at each position is not the served one
    but the one the float8 control puts first there."""
    import numpy as np
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq     # right padding: causal, never read
    d = dims(config)
    with jax.default_matmul_precision("highest"):
        ref = logits_from(params, jnp.asarray(tokens), t0 - 1, rows=rows,
                          **d)
        if control:
            judged = jnp.argmax(logits_from(
                params, jnp.asarray(tokens), t0 - 1, rows=rows,
                precision="fp8", **d), axis=-1)[:n_out]
        else:
            judged = jnp.asarray(np.asarray(seq[t0:t0 + n_out], np.int32))
        ref = ref[:n_out]
        gaps = ref.max(axis=-1) - jnp.take_along_axis(
            ref, judged[:, None], axis=-1)[:, 0]
    return np.asarray(gaps, np.float64)

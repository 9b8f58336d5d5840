"""Plain reference of a decoder-only transformer whose blocks mix the
sequence by POWER RETENTION (``manifestai/Brumby-14B-Base``: the
Qwen3-14B block, pre-norm RMSNorm, SwiGLU, untied head, no biases,
with softmax attention replaced; Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv 2507.04239; "Symmetric Power
Transformers", 2024; the Brumby-14B release note, 2025-10), in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: one sequence, one full
causal forward in the ATTENTION form, no state, no chunks, no kernels.

The layer, per block, input ``x_t`` of width F, ``h = RMSNorm(x)``:

- ``q_t = W_q h`` (H heads of d), ``k_t = W_k h``, ``v_t = W_v h`` (Hkv
  heads of d each); per-head RMSNorm of q and k with learned d-wide
  gains; rotary positions on q and k, feature i turning with feature
  i + d/2 (the half-split pairing);
- gate ``gamma_t = W_gate h + b_gate`` (one a KV head),
  ``log g_t = -softplus(-gamma_t)``, so ``g_t`` lies in (0, 1);
- power ``p = 2``; query head i reads KV head ``i // (H / Hkv)``:
  ``a_tj = (q_t . k_j)^p * exp(sum_{l=j+1..t} log g_l)`` for
  ``j <= t``, ``y_t = sum_j a_tj v_j / (sum_j a_tj + eps)``;
- ``x <- x + W_o concat_i(y)``, then the pre-norm SwiGLU.

(The recurrent form, which this file does not use: ``S_t = g_t
S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} + phi(k_t)``, ``y_t =
phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)``, ``phi(a) . phi(b) =
(a . b)^p``.) What the published ``config.json`` does not carry (the
power, the gate, the normaliser and its eps, the QK-norm) is listed
under ``assumed`` in the configuration file, each with its source.

Imports nothing of the program. It reads a parameter tree by the
zoo's names (``layer_0.W`` the embedding, ``layer_<i>.mha.Wq`` ...
``Wgate``, ``bgate``, ``q_gamma``, ``k_gamma``, ``layer_<L+2>.W`` the
head), which the benchmark made from the seed and may hold in bf16:
every leaf is upcast where it is used, a layer at a time, and the
``T x T`` weights are made one KV group and one block of rows at a
time, so the whole fits beside the weights.

``precision="fp8"`` is the benchmark's control (see PERF.md): every
matrix product takes operands rounded to float8 e4m3 with a
per-tensor scale. It has to come out as not correct.
"""
import functools

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: rows of the ``T x T`` weights made at a time
ROW_BLOCK = 1024


def _operand(x, precision):
    x = x.astype(jnp.float32)
    if precision != "fp8":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, b, precision):
    return _operand(a, precision) @ _operand(b, precision)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        + eps) * gamma.astype(jnp.float32)


def rope(x, theta):
    """x [T, H, D]: feature i turns with feature i + D/2."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def retention(q, k, v, log_g, *, power, ret_eps, precision,
              use_gate=True):
    """The attention form: q [T, H, d], k/v [T, Hkv, d], log_g
    [T, Hkv] -> [T, H, d]. ``use_gate=False`` leaves the gate out (a
    fault the tests inject)."""
    t, n_heads, d = q.shape
    n_kv = k.shape[1]
    groups = n_heads // n_kv
    cum = jnp.cumsum(log_g if use_gate else jnp.zeros_like(log_g),
                     axis=0)                            # [T, Hkv]
    block = min(ROW_BLOCK, t)
    cols = jnp.arange(t)
    out = []
    for kv in range(n_kv):          # one KV group at a time
        qg = q[:, kv * groups:(kv + 1) * groups]        # [T, G, d]
        rows_out = []
        for r0 in range(0, t, block):
            rows = cols[r0:r0 + block]
            s = jnp.einsum("tgd,jd->gtj",
                           _operand(qg[r0:r0 + block], precision),
                           _operand(k[:, kv], precision))
            live = cols[None, :] <= rows[:, None]       # j <= t
            decay = jnp.exp(jnp.where(
                live, cum[r0:r0 + block, kv, None] - cum[None, :, kv],
                -jnp.inf))
            a = s ** power * decay[None]
            num = jnp.einsum("gtj,jd->tgd", _operand(a, precision),
                             _operand(v[:, kv], precision))
            den = jnp.sum(a, axis=-1).T[..., None]      # [t, G, 1]
            rows_out.append(num / (den + ret_eps))
        out.append(jnp.concatenate(rows_out, axis=0))
    return jnp.concatenate(out, axis=1)                 # [T, H, d]


def block(p, x, *, n_heads, n_kv_heads, head_dim, rope_theta, eps,
          power, ret_eps, precision, use_gate=True):
    t = x.shape[0]
    h = rms_norm(x, p["ln1"]["gamma"], eps)
    a = p["mha"]
    q = _mm(h, a["Wq"], precision).reshape(t, n_heads, head_dim)
    k = _mm(h, a["Wk"], precision).reshape(t, n_kv_heads, head_dim)
    v = _mm(h, a["Wv"], precision).reshape(t, n_kv_heads, head_dim)
    q = rope(rms_norm(q, a["q_gamma"], eps), rope_theta)
    k = rope(rms_norm(k, a["k_gamma"], eps), rope_theta)
    gamma = _mm(h, a["Wgate"], precision) + a["bgate"].astype(
        jnp.float32)
    y = retention(q, k, v, -jax.nn.softplus(-gamma), power=power,
                  ret_eps=ret_eps, precision=precision,
                  use_gate=use_gate).reshape(t, -1)
    x = x + _mm(y, a["Wo"], precision) + a["bo"].astype(jnp.float32)
    h = rms_norm(x, p["ln2"]["gamma"], eps)
    h = jax.nn.silu(_mm(h, p["Wg"], precision)) * _mm(h, p["Wu"],
                                                      precision)
    return x + _mm(h, p["Wd"], precision)


@functools.partial(jax.jit, static_argnames=(
    "n_layers", "n_heads", "n_kv_heads", "head_dim", "rope_theta", "eps",
    "power", "ret_eps", "rows", "precision", "use_gate"))
def logits_from(params, tokens, start, *, n_layers, n_heads, n_kv_heads,
                head_dim, rope_theta, eps, power, ret_eps, rows,
                precision="float32", use_gate=True):
    """Next-token logits [rows, V] at positions ``start .. start+rows-1``
    of one sequence ``tokens`` [T], after a full causal forward."""
    x = params["layer_0"]["W"][tokens].astype(jnp.float32)
    for i in range(n_layers):
        x = block(params[f"layer_{i + 1}"], x, n_heads=n_heads,
                  n_kv_heads=n_kv_heads, head_dim=head_dim,
                  rope_theta=rope_theta, eps=eps, power=power,
                  ret_eps=ret_eps, precision=precision,
                  use_gate=use_gate)
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = rms_norm(x, params[f"layer_{n_layers + 1}"]["gamma"], eps)
    head = params[f"layer_{n_layers + 2}"]
    return _mm(x, head["W"], precision) + head["b"].astype(jnp.float32)


def dims(config: dict) -> dict:
    """The reference's sizes: the published configuration keys, and
    what the configuration file lists as assumed."""
    assumed = config["assumed"]
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        power=int(assumed["power"]),
        ret_eps=float(assumed["normaliser_eps"]))


def served_gaps(params, config, seq, t0, n_out, *, pad_to, rows,
                control=False, use_gate=True):
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
                          use_gate=use_gate, **d)
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

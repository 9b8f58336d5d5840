"""Plain reference of a HYBRID decoder: Mamba-2 state-space layers
beside softmax attention layers (``ibm-granite/granite-4.0-h-micro``,
``model_type`` ``granitemoehybrid`` with no routed experts; Dao and Gu,
"Transformers are SSMs", arXiv 2405.21060, for the Mamba-2 mixer), in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: one sequence, one full
causal forward, the recurrence ONE POSITION AT A TIME by ``lax.scan``
(never the chunked form the program admits by: the two must be
independent), the convolution as four shifted products, attention by
the plain causal softmax. No state pool, no chunks, no kernels.

``x_0 = embedding_multiplier * E[token]``. Every layer, pre-norm,
RMSNorm with gain: ``x <- x + residual_multiplier * mixer(RMSNorm(x))``,
then ``x <- x + residual_multiplier * W_d(silu(h W_g) . (h W_u))``, ``h =
RMSNorm(x)`` (the published ``W_in = [W_g | W_u]``, halves in that
order; no bias). Logits ``= RMSNorm(x_L) E^T / logits_scaling`` (tied).

- **Mamba-2 mixer** (``layer_types[i] == "mamba"``), ``H`` heads of
  ``P``, state ``N``, ONE group: ``[z | xBC | dt] = h W_in_proj``,
  widths ``H P | H P + 2 N | H``. ``xBC <- silu(conv(xBC))``: depthwise
  causal convolution over time, ``K`` taps and a bias a channel, ``y_t
  = b + sum_j w_j xBC_{t-K+1+j}``, zeros before the sequence. ``[x | B
  | C] = xBC``, widths ``H P | N | N``. ``Delta_t = softplus(dt_t +
  dt_bias)`` a head (no clamp), ``a_t = exp(Delta_t A)``, ``A =
  -exp(A_log)``. ``H_t = a_t H_{t-1} + Delta_t x_t (x) B_t`` (a head
  ``[P, N]``, float32), ``y_t = H_t C_t + D x_t``. ``y <- RMSNorm_{H
  P}(y . silu(z))`` with a gain and eps of its own (the gate BEFORE the
  norm, one group over all features), then ``W_out_proj``.
- **Attention** (``"attention"``): ``q, k, v = h W_q, h W_k, h W_v``
  (no bias, NO positional term of any kind), scores ``q . k *
  attention_multiplier`` (not ``d^-1/2``), causal softmax in float32,
  ``W_o``.

What the published ``config.json`` does not carry is listed under
``assumed`` in the configuration file, each with its reason.

Imports nothing of the program. It reads a parameter tree by the
zoo's names (``layer_0.W`` the embedding and tied head,
``layer_<i>.mha`` a mixer's leaves: ``Win``, ``conv_w [K, channels]``,
``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``norm_gamma``, ``Wo``, or
``Wq``, ``Wk``, ``Wv``, ``Wo``, ``bo``; ``ln1``/``ln2``, ``Wg``,
``Wu``, ``Wd``), which the benchmark made from the seed and may hold
in bf16: ONE layer's leaves are upcast at a time (each layer is a
jitted call of its own, so the whole fits beside the weights and
compiles in seconds), and the ``T x T`` scores are made one KV group
at a time.

``precision="fp8"`` is the benchmark's control (see PERF.md): every
matrix product takes operands rounded to float8 e4m3 with a
per-tensor scale. It has to come out as not correct.
"""
import functools

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


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


def causal_conv(xbc, w, b):
    """``y_t = b + sum_j w[j] xbc_{t-K+1+j}`` as ``K`` shifted
    products; ``xbc [T, C]``, ``w [K, C]``, zeros before the
    sequence."""
    t, taps = xbc.shape[0], w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    y = b.astype(jnp.float32)[None, :]
    for j in range(taps):
        y = y + w[j].astype(jnp.float32)[None, :] * padded[j:j + t]
    return y


def recurrence(x, b, c, delta, a_neg, d_skip):
    """One position at a time: ``x [T, H, P]``, ``b``/``c`` ``[T, N]``,
    ``delta [T, H]``, ``a_neg``/``d_skip`` ``[H]`` -> ``[T, H, P]``."""
    n_heads, p = x.shape[1:]

    def step(state, at):
        x_t, b_t, c_t, delta_t = at
        a_t = jnp.exp(delta_t * a_neg)                  # [H]
        state = (a_t[:, None, None] * state
                 + (delta_t[:, None] * x_t)[:, :, None]
                 * b_t[None, None, :])
        y_t = jnp.sum(state * c_t[None, None, :], axis=-1)
        return state, y_t + d_skip[:, None] * x_t

    _, y = jax.lax.scan(
        step, jnp.zeros((n_heads, p, b.shape[1]), jnp.float32),
        (x, b, c, delta))
    return y


def mamba_mixer(a, h, *, n_heads, d_state, norm_eps, precision):
    t = h.shape[0]
    d_inner = a["Wo"].shape[0]
    zxbcdt = _mm(h, a["Win"], precision)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * d_state]
    dt = zxbcdt[:, 2 * d_inner + 2 * d_state:]
    xbc = jax.nn.silu(causal_conv(xbc, a["conv_w"], a["conv_b"]))
    x = xbc[:, :d_inner].reshape(t, n_heads, -1)
    b = xbc[:, d_inner:d_inner + d_state]
    c = xbc[:, d_inner + d_state:]
    delta = jax.nn.softplus(dt + a["dt_bias"].astype(jnp.float32))
    y = recurrence(x, b, c, delta,
                   -jnp.exp(a["A_log"].astype(jnp.float32)),
                   a["D"].astype(jnp.float32)).reshape(t, d_inner)
    y = rms_norm(y * jax.nn.silu(z), a["norm_gamma"], norm_eps)
    return _mm(y, a["Wo"], precision)


def attention_mixer(a, h, *, n_heads, n_kv_heads, score_scale, precision):
    t = h.shape[0]
    q = _mm(h, a["Wq"], precision).reshape(t, n_heads, -1)
    k = _mm(h, a["Wk"], precision).reshape(t, n_kv_heads, -1)
    v = _mm(h, a["Wv"], precision).reshape(t, n_kv_heads, -1)
    groups = n_heads // n_kv_heads
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    out = []
    for kv in range(n_kv_heads):        # one KV group at a time
        qg = q[:, kv * groups:(kv + 1) * groups]
        s = jnp.einsum("tgd,jd->gtj", _operand(qg, precision),
                       _operand(k[:, kv], precision)) * score_scale
        w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("gtj,jd->tgd", _operand(w, precision),
                              _operand(v[:, kv], precision)))
    y = jnp.concatenate(out, axis=1).reshape(t, -1)
    return _mm(y, a["Wo"], precision) + a["bo"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "kind", "n_heads", "n_kv_heads", "mamba_heads", "d_state", "eps",
    "norm_eps", "residual", "score_scale", "precision"))
def layer(p, x, *, kind, n_heads, n_kv_heads, mamba_heads, d_state, eps,
          norm_eps, residual, score_scale, precision="float32"):
    """One layer of its kind over the whole sequence ``x [T, F]``; its
    leaves are upcast here and nowhere else."""
    h = rms_norm(x, p["ln1"]["gamma"], eps)
    if kind == "mamba":
        a = mamba_mixer(p["mha"], h, n_heads=mamba_heads, d_state=d_state,
                        norm_eps=norm_eps, precision=precision)
    else:
        a = attention_mixer(p["mha"], h, n_heads=n_heads,
                            n_kv_heads=n_kv_heads,
                            score_scale=score_scale, precision=precision)
    x = x + residual * a
    h = rms_norm(x, p["ln2"]["gamma"], eps)
    h = jax.nn.silu(_mm(h, p["Wg"], precision)) * _mm(h, p["Wu"],
                                                      precision)
    return x + residual * _mm(h, p["Wd"], precision)


@functools.partial(jax.jit, static_argnames=("multiplier",))
def embed(table, tokens, *, multiplier):
    return multiplier * table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("rows", "eps", "scaling",
                                             "precision"))
def head(table, gamma, bias, x, start, *, rows, eps, scaling,
         precision="float32"):
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = rms_norm(x, gamma, eps)
    return (_mm(x, table.T, precision)
            + bias.astype(jnp.float32)) / scaling


def logits_from(params, tokens, start, *, kinds, rows, eps, multiplier,
                scaling, precision="float32", **sizes):
    """Next-token logits [rows, V] at positions ``start .. start+rows-1``
    of one sequence ``tokens`` [T], after a full causal forward."""
    table = params["layer_0"]["W"]
    x = embed(table, tokens, multiplier=multiplier)
    for i, kind in enumerate(kinds):
        x = layer(params[f"layer_{i + 1}"], x, kind=kind, eps=eps,
                  precision=precision, **sizes)
    n = len(kinds)
    return head(table, params[f"layer_{n + 1}"]["gamma"],
                params[f"layer_{n + 2}"]["b"], x, start, rows=rows,
                eps=eps, scaling=scaling, precision=precision)


def dims(config: dict) -> dict:
    """The reference's sizes and scalars: the published keys."""
    kinds = tuple(config["layer_types"])
    if set(kinds) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {sorted(set(kinds))}")
    if (config["num_local_experts"] or config["mamba_n_groups"] != 1
            or config["position_embedding_type"] != "nope"
            or not config["tie_word_embeddings"]
            or config["attention_bias"] or config["mamba_proj_bias"]):
        raise ValueError("this reference reads a dense hybrid with one "
                         "group, no positions, no biases, a tied head")
    return dict(
        kinds=kinds, eps=float(config["rms_norm_eps"]),
        norm_eps=float(config["rms_norm_eps"]),
        multiplier=float(config["embedding_multiplier"]),
        residual=float(config["residual_multiplier"]),
        scaling=float(config["logits_scaling"]),
        score_scale=float(config["attention_multiplier"]),
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        mamba_heads=config["mamba_n_heads"],
        d_state=config["mamba_d_state"])


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

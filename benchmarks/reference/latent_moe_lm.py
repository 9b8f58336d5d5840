"""Plain reference of a decoder-only transformer whose blocks attend
through a LATENT (multi-head latent attention, MLA) and whose
feed-forward is a MIXTURE OF EXPERTS beside a shared expert
(``deepseek-ai/DeepSeek-V3``: DeepSeek-V2, arXiv 2405.04434, section
2.1; DeepSeek-V3, arXiv 2412.19437, sections 2.1 and 2.2), as ONE CHIP
OF AN EXPERT-PARALLEL GROUP computes it, in straightforward float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: one
sequence, one full causal forward in the EXPANDED form. No absorbed
product, no cache, no sort: every held expert is applied to every row
and masked by the routing.

The block, pre-norm, ``eps`` as published; input ``x_t`` of width F:

- **MLA.** ``h = RMSNorm(x)``. ``c_q = RMSNorm(h W_qa)``; ``q = c_q
  W_qb``: H heads of ``[q_nope | q_rope]``. ``[c_kv | k_rope] = h
  W_kva``; ``c_kv <- RMSNorm(c_kv)``; ``k_rope`` is ONE key shared by
  all heads. Rotary positions on ``q_rope`` and ``k_rope`` with YaRN
  frequencies (per frequency a linear ramp between the interpolated
  and the original frequency over the correction range of
  ``beta_fast``/``beta_slow`` turns in the original context), features
  (2i, 2i+1) paired. ``[k_nope | v] = c_kv W_kvb``. Scores ``(q_nope .
  k_nope + q_rope . k_rope) (nope + rope)^-1/2 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``; causal softmax; ``a = softmax . v``;
  ``x <- x + a W_o``.
- **Feed-forward.** The first ``first_k_dense_replace`` layers: SwiGLU
  of ``intermediate_size``. The others: ``s = sigmoid(h W_r)`` over
  ALL the published experts' outputs; the choice goes by ``s + b``
  (``b`` the score-correction bias): a group's score is the sum of
  its two largest, the best ``topk_group`` of ``n_group`` groups stay,
  the best ``num_experts_per_tok`` experts inside them are chosen
  (ties to the lower index); weights = the chosen experts' ``s``
  (without ``b``), normalised to sum 1, times
  ``routed_scaling_factor``. ``y = shared(h) + sum_{e chosen, e held
  here} w_e expert_e(h)``, each a SwiGLU of ``moe_intermediate_size``.
  Experts chosen but not held add nothing: their chips would.

What the published ``config.json`` does not settle (the rotary
pairing, the zero correction bias) is under ``assumed`` in the
configuration file; the multi-token-prediction module is not part of
the forward (``not_served``).

**Routing under two precisions.** A program in bf16 moves a score by
a little; where a decision that involves a held expert lies closer
than that, program and reference may choose differently and that
position's logits part by far more than rounding. So the reference
gives every compared position its smallest **routing margin** over
the expert layers (:func:`route`), and :func:`served_gaps` leaves
positions under the configuration's ``tie_margin`` out of the gap;
their share is a check of its own, ``routing_tie_share``. The
reference is never forced onto the program's routes.

Imports nothing of the program. It reads a parameter tree by the
zoo's names (``layer_0.W`` the embedding; ``layer_<i>.mha.Wqa``,
``qa_gamma``, ``Wqb``, ``Wkva``, ``kv_gamma``, ``Wkvb``, ``Wo``;
``Wg``/``Wu``/``Wd`` of a dense layer; ``moe.Wr``, ``br``,
``Weg``/``Weu``/``Wed [n_held, ...]``, ``Wsg``/``Wsu``/``Wsd`` of an
expert layer; ``layer_<L+2>.W`` the head), which the benchmark made
from the seed and may hold in bf16: every leaf is upcast where it is
used, ONE LAYER (and one expert) at a time, each layer a program of
its own, and scores are made one group of heads and one block of
rows at a time, so the whole fits beside the weights.

``precision="fp8"`` is the benchmark's control (see PERF.md): every
matrix product takes operands rounded to float8 e4m3 with a
per-tensor scale. It has to come out as not correct.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: rows of the ``T x T`` scores made at a time
ROW_BLOCK = 1024
#: heads whose scores are made at a time
HEAD_BLOCK = 16
#: tie margins whose share and gap a run logs beside the configured
#: one (what the limit and the margin are set from)
LOGGED_MARGINS = (0.0, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05)


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


def yarn_inv_freq(d: dict) -> np.ndarray:
    """The ``rope / 2`` rotary frequencies under the configuration's
    ``rope_scaling`` (plain ``theta^(-2i/dim)`` without one)."""
    dim, theta, y = d["rope"], d["rope_theta"], d["yarn"]
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if y is None:
        return (1.0 / pos_freqs).astype(np.float32)

    def correction_dim(turns):
        return dim * math.log(
            y["original_max_position_embeddings"]
            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / (y["factor"] * pos_freqs)) * ramp + (
        1.0 / pos_freqs) * (1.0 - ramp)
    return inv.astype(np.float32)


def softmax_scale(d: dict) -> float:
    scale = (d["nope"] + d["rope"]) ** -0.5
    y = d["yarn"]
    if y is not None and y.get("mscale_all_dim"):
        m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
        scale *= m * m
    return scale


def rope(x, inv_freq):
    """``x [T, ..., rope]`` at positions ``0 .. T-1``: feature 2i
    turns with feature 2i + 1."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), -1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def attention(a, h, d, precision, norm_latent=True):
    """MLA over one whole sequence ``h [T, F]``, expanded form.
    ``norm_latent=False`` leaves the latent's norm out (a fault the
    tests inject)."""
    t = h.shape[0]
    n_heads, nope, v_dim = d["n_heads"], d["nope"], d["v"]
    eps = d["eps"]
    inv_freq = jnp.asarray(yarn_inv_freq(d))
    cq = rms_norm(_mm(h, a["Wqa"], precision), a["qa_gamma"], eps)
    q = _mm(cq, a["Wqb"], precision).reshape(t, n_heads, -1)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], inv_freq)
    kva = _mm(h, a["Wkva"], precision)
    c_kv, k_rope = kva[:, :d["kv_rank"]], rope(kva[:, d["kv_rank"]:],
                                                inv_freq)
    if norm_latent:
        c_kv = rms_norm(c_kv, a["kv_gamma"], eps)
    kv = _mm(c_kv, a["Wkvb"], precision).reshape(t, n_heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(d)
    block = min(ROW_BLOCK, t)
    hb = min(HEAD_BLOCK, n_heads)
    cols = jnp.arange(t)

    def heads(h0):
        sl = lambda z: jax.lax.dynamic_slice_in_dim(z, h0, hb, axis=1)
        qn, qr, kn, vv = sl(q_nope), sl(q_rope), sl(k_nope), sl(v)

        def rows(r0):
            rsl = lambda z: jax.lax.dynamic_slice_in_dim(z, r0, block,
                                                         axis=0)
            s = (jnp.einsum("qhd,khd->hqk",
                            _operand(rsl(qn), precision),
                            _operand(kn, precision))
                 + jnp.einsum("qhr,kr->hqk",
                              _operand(rsl(qr), precision),
                              _operand(k_rope, precision))) * scale
            live = cols[None, :] <= (r0 + jnp.arange(block))[:, None]
            w = jax.nn.softmax(jnp.where(live[None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("hqk,khd->qhd", _operand(w, precision),
                              _operand(vv, precision))

        out = jax.lax.map(rows, jnp.arange(0, t, block))
        return out.reshape(t, hb, v_dim)

    out = jax.lax.map(heads, jnp.arange(0, n_heads, hb))  # [G,T,hb,v]
    out = out.transpose(1, 0, 2, 3).reshape(t, n_heads * v_dim)
    return _mm(out, a["Wo"], precision)


def _ranked(x):
    """Indices by falling value, ties to the lower index."""
    return jnp.argsort(-x, axis=-1, stable=True)


def route(s, bias, d):
    """Scores ``s [T, E]`` to ``(ids [T, k], weights [T, k], margin
    [T])``. ``margin`` is how far, in score, the nearest decision that
    involves a HELD expert lies from going the other way: a held
    candidate's distance from the boundary of the chosen set, a group
    with held experts' distance from the boundary of the kept groups,
    and the gap between the last kept and the first dropped group
    where either outcome chooses a held expert. ``inf`` where no held
    expert is near any choice."""
    k, n_group, topk_group = d["top_k"], d["n_group"], d["topk_group"]
    t, e = s.shape
    per = e // n_group
    c = s + bias.astype(jnp.float32)
    group = jnp.sort(c.reshape(t, n_group, per),
                     axis=-1)[..., -2:].sum(-1)            # [T, G]
    g_rank = _ranked(group)

    def choose(kept):
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group),
                       axis=1)
        cm = jnp.where(jnp.repeat(keep, per, axis=1), c, -jnp.inf)
        return cm, _ranked(cm)

    cm, e_rank = choose(g_rank[:, :topk_group])
    ids = e_rank[:, :k]
    w = jnp.take_along_axis(s, ids, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * d["scale"]

    held = jnp.arange(d["offset"], d["offset"] + d["n_held"])
    inf = jnp.full((t,), jnp.inf)
    take = lambda x, r: jnp.take_along_axis(x, r, axis=1)[:, 0]
    last_in = take(cm, e_rank[:, k - 1:k])
    first_out = take(cm, e_rank[:, k:k + 1])
    c_held = cm[:, held]
    held_in = jnp.any(ids[:, :, None] == held[None, None, :], axis=1)
    m_expert = jnp.min(jnp.where(
        jnp.isfinite(c_held),
        jnp.where(held_in, c_held - first_out[:, None],
                  last_in[:, None] - c_held), jnp.inf), axis=1)
    margin = m_expert
    if n_group > topk_group:
        g_in = take(group, g_rank[:, topk_group - 1:topk_group])
        g_out = take(group, g_rank[:, topk_group:topk_group + 1])
        for hg in sorted({x // per for x in range(
                d["offset"], d["offset"] + d["n_held"])}):
            kept = jnp.any(g_rank[:, :topk_group] == hg, axis=1)
            margin = jnp.minimum(margin, jnp.where(
                kept, group[:, hg] - g_out, g_in - group[:, hg]))
        swapped = jnp.concatenate(
            [g_rank[:, :topk_group - 1],
             g_rank[:, topk_group:topk_group + 1]], axis=1)
        alt_in = jnp.any(choose(swapped)[1][:, :k, None]
                         == held[None, None, :], axis=(1, 2))
        margin = jnp.minimum(margin, jnp.where(
            jnp.any(held_in, axis=1) | alt_in, g_in - g_out, inf))
    return ids, w, margin


def experts_ffn(p, h, d, precision, drop_route=False,
                bf16_router=False):
    """The expert layer over ``h [T, F]``: ``(y, margin [T])``.
    ``drop_route`` leaves each token's last chosen expert out and
    ``bf16_router`` scores in bf16 (faults the tests inject)."""
    if bf16_router:
        logits = (h.astype(jnp.bfloat16)
                  @ p["Wr"].astype(jnp.bfloat16)).astype(jnp.float32)
    else:
        logits = _mm(h, p["Wr"], precision)
    ids, w, margin = route(jax.nn.sigmoid(logits), p["br"], d)
    if drop_route:
        w = w.at[:, -1].set(0.0)
    local = jnp.arange(d["offset"], d["offset"] + d["n_held"])
    w_held = jnp.sum(w[:, :, None] * (ids[:, :, None] == local),
                     axis=1)                                # [T, held]

    def swiglu(x, wg, wu, wd):
        return _mm(jax.nn.silu(_mm(x, wg, precision))
                   * _mm(x, wu, precision), wd, precision)

    def one(acc, xs):       # every held expert on every row, masked
        wg, wu, wd, col = xs
        return acc + col[:, None] * swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["Weg"], p["Weu"], p["Wed"], w_held.T))
    if "Wsg" in p:
        y = swiglu(h, p["Wsg"], p["Wsu"], p["Wsd"]) + y
    return y, margin


@functools.partial(jax.jit, static_argnames=("d", "precision", "faults"))
def block(p, x, *, d, precision="float32", faults=()):
    """One block over a whole sequence ``x [T, F]``: ``(x, margin
    [T])`` (``inf`` for a dense layer)."""
    d = dict(d)
    x = x + attention(p["mha"], rms_norm(x, p["ln1"]["gamma"], d["eps"]),
                      d, precision,
                      norm_latent="raw_latent" not in faults)
    h = rms_norm(x, p["ln2"]["gamma"], d["eps"])
    if "moe" in p:
        y, margin = experts_ffn(p["moe"], h, d, precision,
                                drop_route="drop_route" in faults,
                                bf16_router="bf16_router" in faults)
    else:
        y = _mm(jax.nn.silu(_mm(h, p["Wg"], precision))
                * _mm(h, p["Wu"], precision), p["Wd"], precision)
        margin = jnp.full((x.shape[0],), jnp.inf)
    return x + y, margin


@functools.partial(jax.jit, static_argnames=("rows", "eps", "precision"))
def head(norm, out, x, start, *, rows, eps, precision="float32"):
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = rms_norm(x, norm["gamma"], eps)
    return _mm(x, out["W"], precision) + out["b"].astype(jnp.float32)


def logits_from(params, tokens, start, *, d, rows, precision="float32",
                faults=()):
    """Next-token logits ``[rows, V]`` at positions ``start .. start +
    rows - 1`` of one sequence ``tokens [T]`` after a full causal
    forward, and those positions' smallest routing margin over the
    expert layers. Each layer is a program of its own, so one layer's
    float32 copy is all that lies beside the weights."""
    n_layers = d["n_layers"]
    key = tuple(sorted(d.items()))
    x = params["layer_0"]["W"][tokens].astype(jnp.float32)
    margin = jnp.full((tokens.shape[0],), jnp.inf)
    for i in range(n_layers):
        x, m = block(params[f"layer_{i + 1}"], x, d=key,
                     precision=precision, faults=tuple(faults))
        margin = jnp.minimum(margin, m)
    logits = head(params[f"layer_{n_layers + 1}"],
                  params[f"layer_{n_layers + 2}"], x, start, rows=rows,
                  eps=d["eps"], precision=precision)
    return logits, jax.lax.dynamic_slice_in_dim(margin, start, rows)


def dims(config: dict) -> dict:
    """The reference's sizes: the published configuration's keys."""
    yarn = config.get("rope_scaling")
    return dict(
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        yarn=None if yarn is None else _Frozen(yarn),
        eps=float(config["rms_norm_eps"]),
        top_k=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        scale=float(config["routed_scaling_factor"]),
        n_held=config["n_routed_experts"],
        offset=int(config.get("expert_offset", 0)))


class _Frozen(dict):
    """A hashable view of a nested group (a static jit argument)."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def served_gaps(params, config, seq, t0, n_out, *, pad_to, rows,
                control=False, faults=(), log=print):
    """How far below the reference's best logit each served token lies.

    ``seq`` is one request's prompt (``t0`` tokens) followed by its
    ``n_out`` served tokens. The reference runs once over it, teacher
    forced; position ``t0 - 1 + j`` predicts served token ``j``.
    Returns the gaps, in the reference's float32 logits, of the
    positions whose routing margin is at least the configuration's
    ``tie_margin``; the share left out is checked here against
    ``routing_tie_share``'s limit and printed beside it, and a share
    over the limit makes the gap infinite. With ``control`` the token
    judged at each position is not the served one but the one the
    float8 control puts first there."""
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq     # right padding: causal, never read
    d = dims(config)
    with jax.default_matmul_precision("highest"):
        ref, margin = logits_from(params, jnp.asarray(tokens), t0 - 1,
                                  d=d, rows=rows, faults=faults)
        if control:
            judged = jnp.argmax(logits_from(
                params, jnp.asarray(tokens), t0 - 1, d=d, rows=rows,
                precision="fp8")[0], axis=-1)[:n_out]
        else:
            judged = jnp.asarray(np.asarray(seq[t0:t0 + n_out], np.int32))
        ref = ref[:n_out]
        gaps = ref.max(axis=-1) - jnp.take_along_axis(
            ref, judged[:, None], axis=-1)[:, 0]
    gaps = np.asarray(gaps, np.float64)
    margin = np.asarray(margin, np.float64)[:n_out]
    limits = config["correct"]
    tie_margin = limits["routing_tie_share"]["tie_margin"]
    if not control:
        log("routing margins of %d positions: " % n_out + ", ".join(
            "under %g: %.4f of them, widest gap outside %.4f" % (
                m, np.mean(margin < m),
                gaps[margin >= m].max(initial=0.0))
            for m in LOGGED_MARGINS))
    tied = margin < tie_margin
    share, limit = float(np.mean(tied)), limits["routing_tie_share"]["limit"]
    ok = share <= limit
    if not control:
        log(f"check routing_tie_share: value={share!r} limit={limit!r} "
            f"{'ok' if ok else 'NOT CORRECT'}")
    if not ok or tied.all():
        return np.asarray([np.inf])
    return gaps[~tied]

"""Plain reference of a decoder-only transformer whose softmax layers
are of two kinds, SLIDING-WINDOW layers with rotary positions beside
FULL layers without any positional term, and whose feed-forward is a
MIXTURE OF small ReGLU EXPERTS routed BEFORE the attention
(``PowerInfer/SmallThinker-21BA3B-Instruct``), in straightforward
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``:
one sequence, one full causal forward. No cache, no kernel, no
batching, no sort; scores are made a block of rows at a time so that
9,728 positions fit, and each expert is applied to the rows that chose
it (a loop over the experts; never every expert on every row).

The block, pre-norm, ``eps`` as published; ``x`` is ``[T, F]``:

    a   = RMSNorm(x; g1)
    r   = a W_r                      float32: the router reads the
                                     PRE-attention rows
    q   = a W_q -> H heads of D;  k, v = a W_k, a W_v -> Hkv heads of D
    if rope_layout[l]:  q, k rotated (theta; feature i turns with
                        feature i + D/2), else no positional term
    key j is visible to query t  iff  j <= t  and
        (sliding_window_layout[l] == 0  or  j > t - window)
    o   = softmax(q k^T / sqrt(D)) v, heads joined, times W_o
    x'  = x + o
    b   = RMSNorm(x'; g2)
    ids = the k largest of r (ties to the lower index)
    w   = softmax(r[ids])            (softmax over all E renormalised
                                     over the chosen is the same)
    y   = sum_{e in ids} w_e (relu(b Wg_e) * (b Wu_e)) Wd_e
    x'' = x' + y

then the final RMSNorm and an untied head. ``rope_layout`` and
``sliding_window_layout`` are read each for itself.

Departures from the published description, all under ``assumed`` in
the configuration file: no attention biases, no QK norm; the half-split
rotary pairing; a window that counts the query's own position (the
``transformers`` mask's rule); no secondary experts (``config.json``
has no key for them).

**Routing under two precisions** (as ``latent_moe_lm``): a bf16
program moves a router logit by a little; where the ``k``-th and the
``k + 1``-th largest lie closer than that the two may choose
differently, and that position's logits part by far more than
rounding. The reference gives every compared position its smallest
**routing margin** over the layers (that difference, in logits), and
:func:`served_gaps` leaves positions under the configuration's
``tie_margin`` out of the gap; their share is a check of its own,
``routing_tie_share``. The reference is never forced onto the
program's routes.

Imports nothing of the program. It reads a parameter tree by the zoo's
names (``layer_0.W`` the embedding; ``layer_<i>.mha.Wq``/``Wk``/``Wv``/
``Wo``, ``ln1``/``ln2.gamma``, ``moe.Wr``, ``Weg``/``Weu``/``Wed [E,
...]``; ``layer_<L+1>.gamma``; ``layer_<L+2>.W``/``b`` the head), which
the benchmark made from the seed and may hold in bf16: every leaf is
upcast where it is used, one layer and one expert at a time.

``precision="fp8"`` is the benchmark's control (see PERF.md): every
matrix product takes operands rounded to float8 e4m3 with a per-tensor
scale. It has to come out as not correct. ``faults`` (names below) are
what the tests inject to show that the comparison sees them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: rows of the ``T x T`` scores made at a time
ROW_BLOCK = 512
#: an expert's rows are padded to this times a power of two (one
#: program a size, a handful of sizes, not one a count)
EXPERT_ROWS = 256
#: tie margins whose share and gap a run logs beside the configured
#: one (what the limit and the margin are set from)
LOGGED_MARGINS = (0.0, 0.005, 0.01, 0.02, 0.03, 0.04, 0.05, 0.1)
#: what the tests may inject (each must fail the comparison)
FAULTS = ("no_window", "window_off_by_one_page", "rope_on_full",
          "no_rope_on_window", "post_attention_router", "silu",
          "no_renorm", "drop_route", "bf16_router")


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
    """``x [T, heads, D]`` at positions ``0 .. T-1``: feature ``i``
    turns with feature ``i + D/2``."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "theta", "window", "eps", "precision"))
def attention_half(p, x, *, n_heads, n_kv, theta, window, eps,
                   precision="float32"):
    """``(x', a, r)``: the rows after the attention's residual, the
    pre-attention normed rows and the router's logits over them."""
    t = x.shape[0]
    mha = p["mha"]
    a = rms_norm(x, p["ln1"]["gamma"], eps)
    r = _mm(a, p["moe"]["Wr"], precision)
    q = _mm(a, mha["Wq"], precision).reshape(t, n_heads, -1)
    k = _mm(a, mha["Wk"], precision).reshape(t, n_kv, -1)
    v = _mm(a, mha["Wv"], precision).reshape(t, n_kv, -1)
    if theta is not None:
        q, k = rope(q, theta), rope(k, theta)
    d = q.shape[-1]
    g = n_heads // n_kv
    qg = q.reshape(t, n_kv, g, d)
    block = min(ROW_BLOCK, t)
    cols = jnp.arange(t)

    def rows(r0):
        qb = jax.lax.dynamic_slice_in_dim(qg, r0, block, axis=0)
        s = jnp.einsum("qkgd,tkd->kgqt", _operand(qb, precision),
                       _operand(k, precision)) / jnp.sqrt(
                           jnp.float32(d))
        at = (r0 + jnp.arange(block))[:, None]
        live = cols[None, :] <= at
        if window is not None:
            live = live & (cols[None, :] > at - window)
        w = jax.nn.softmax(jnp.where(live[None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", _operand(w, precision),
                          _operand(v, precision))

    o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, -1)
    return x + _mm(o, mha["Wo"], precision), a, r


def _ranked(x):
    """Indices by falling value, ties to the lower index."""
    return jnp.argsort(-x, axis=-1, stable=True)


@functools.partial(jax.jit, static_argnames=("top_k", "renorm", "drop"))
def route(r, *, top_k, renorm=True, drop=False):
    """Router logits ``r [T, E]`` to ``(ids [T, k], weights [T, k],
    margin [T])``: the ``k`` largest, weighed by the softmax over them;
    ``margin`` the ``k``-th largest less the next, in logits."""
    rank = _ranked(r)
    ids = rank[:, :top_k]
    top = jnp.take_along_axis(r, rank[:, :top_k + 1], axis=1)
    if renorm:
        w = jax.nn.softmax(top[:, :top_k], axis=-1)
    else:       # a fault: the softmax over all E, not renormalised
        w = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), ids, axis=1)
    if drop:    # a fault: each token's last chosen expert left out
        w = w.at[:, -1].set(0.0)
    return ids, w, top[:, top_k - 1] - top[:, top_k]


@functools.partial(jax.jit, static_argnames=("eps",))
def second_norm(p, x, *, eps):
    return rms_norm(x, p["ln2"]["gamma"], eps)


@functools.partial(jax.jit, static_argnames=("cap", "act", "precision"))
def expert_rows(y, b, chose, weight, wg, wu, wd, *, cap, act,
                precision="float32"):
    """``y`` with ONE expert's part added: the expert applied to the
    rows that chose it (``cap`` of them at most, a static size)."""
    t = b.shape[0]
    (idx,) = jnp.nonzero(chose, size=cap, fill_value=t)
    rows = jnp.concatenate([b, jnp.zeros_like(b[:1])])[idx]
    gate = {"relu": jax.nn.relu, "silu": jax.nn.silu}[act]
    out = _mm(gate(_mm(rows, wg, precision)) * _mm(rows, wu, precision),
              wd, precision)
    w = jnp.concatenate([weight, jnp.zeros_like(weight[:1])])[idx]
    return y.at[idx].add(w[:, None] * out, mode="drop")


def experts_half(p, x, route_rows, n_real, d, precision, faults):
    """``(x'', margin [T])``: the expert layer over the rows after the
    attention, routed by ``route_rows``' logits. Rows at and past
    ``n_real`` (right padding, never read by a real row) choose no
    expert."""
    b = second_norm(p, x, eps=d["eps"])
    moe = p["moe"]
    if "post_attention_router" in faults:
        route_rows = _mm(b, moe["Wr"], precision)
    if "bf16_router" in faults:
        route_rows = route_rows.astype(jnp.bfloat16).astype(jnp.float32)
    ids, w, margin = route(route_rows, top_k=d["top_k"],
                           renorm="no_renorm" not in faults,
                           drop="drop_route" in faults)
    real = jnp.arange(x.shape[0]) < n_real
    n_e = moe["Weg"].shape[0]
    chose = (ids[:, :, None] == jnp.arange(n_e)) & real[:, None, None]
    w_e = jnp.sum(w[:, :, None] * chose, axis=1)            # [T, E]
    chose = jnp.any(chose, axis=1)                           # [T, E]
    counts = np.asarray(jnp.sum(chose, axis=0))
    y = jnp.zeros_like(x)
    act = "silu" if "silu" in faults else "relu"
    for e in range(n_e):
        if not counts[e]:
            continue
        cap = EXPERT_ROWS
        while cap < counts[e]:
            cap *= 2
        y = expert_rows(y, b, chose[:, e], w_e[:, e], moe["Weg"][e],
                        moe["Weu"][e], moe["Wed"][e], cap=cap, act=act,
                        precision=precision)
    return x + y, margin


@functools.partial(jax.jit, static_argnames=("rows", "eps", "precision"))
def head(norm, out, x, start, *, rows, eps, precision="float32"):
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = rms_norm(x, norm["gamma"], eps)
    return _mm(x, out["W"], precision) + out["b"].astype(jnp.float32)


def layer_rules(d: dict, li: int, faults=()):
    """``(theta or None, window or None)`` of layer ``li``."""
    rotated = bool(d["rope_layout"][li])
    windowed = bool(d["window_layout"][li])
    if "rope_on_full" in faults and not windowed:
        rotated = True
    if "no_rope_on_window" in faults and windowed:
        rotated = False
    window = d["window"] if windowed else None
    if window is not None and "no_window" in faults:
        window = None
    if window is not None and "window_off_by_one_page" in faults:
        window = window - 16
    return (d["rope_theta"] if rotated else None), window


def logits_from(params, tokens, start, *, d, rows, n_real=None,
                precision="float32", faults=()):
    """Next-token logits ``[rows, V]`` at positions ``start .. start +
    rows - 1`` of one sequence ``tokens [T]`` after a full causal
    forward, and those positions' smallest routing margin over the
    layers. Each layer's halves are programs of their own, so one
    layer's float32 copies are all that lie beside the weights."""
    n_layers = d["n_layers"]
    n_real = tokens.shape[0] if n_real is None else n_real
    x = params["layer_0"]["W"][tokens].astype(jnp.float32)
    margin = jnp.full((tokens.shape[0],), jnp.inf)
    for i in range(n_layers):
        p = params[f"layer_{i + 1}"]
        theta, window = layer_rules(d, i, faults)
        x, _, r = attention_half(
            p, x, n_heads=d["n_heads"], n_kv=d["n_kv"], theta=theta,
            window=window, eps=d["eps"], precision=precision)
        x, m = experts_half(p, x, r, n_real, d, precision, faults)
        margin = jnp.minimum(margin, m)
    logits = head(params[f"layer_{n_layers + 1}"],
                  params[f"layer_{n_layers + 2}"], x, start, rows=rows,
                  eps=d["eps"], precision=precision)
    return logits, jax.lax.dynamic_slice_in_dim(margin, start, rows)


def dims(config: dict) -> dict:
    """The reference's sizes: the published configuration's keys."""
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the reference weighs the chosen experts by "
                         "the softmax over them")
    n = config["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        if len(config[key]) < n:
            raise ValueError(f"{key} names {len(config[key])} layers "
                             f"of {n}")
    return dict(
        n_layers=n, n_heads=config["num_attention_heads"],
        n_kv=config["num_key_value_heads"],
        rope_theta=float(config["rope_theta"]),
        rope_layout=tuple(config["rope_layout"][:n]),
        window_layout=tuple(config["sliding_window_layout"][:n]),
        window=config["sliding_window_size"],
        eps=float(config["rms_norm_eps"]),
        top_k=config["moe_num_active_primary_experts"])


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
                                  d=d, rows=rows, n_real=len(seq),
                                  faults=faults)
        if control:
            judged = jnp.argmax(logits_from(
                params, jnp.asarray(tokens), t0 - 1, d=d, rows=rows,
                n_real=len(seq), precision="fp8")[0], axis=-1)[:n_out]
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

"""Plain reference of a decoder-only transformer whose softmax layers
are of two kinds that differ in more than their window: FULL layers of
48 heads whose first 64 features a head turn by YaRN frequencies,
beside SLIDING-WINDOW layers of 64 heads turned whole by plain
frequencies; a sigmoid GATE a head on the attention's output; and,
after one leading dense layer, a MIXTURE OF tiny SwiGLU EXPERTS beside
a shared one (``poolside/Laguna-XS.2``), in straightforward float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``: one
sequence, one full causal forward. No cache, no kernel, no batching,
no sort; scores are made a block of rows at a time so that 11,264
positions fit, and each expert is applied to the rows that chose it (a
scan over the experts; never every expert on every row). A sequence
is right-padded to the shortest of a few lengths that holds it, not to
the gateway's whole context (:func:`padded_sizes`: the forward is
causal, so the padding is never read).

The block, pre-norm, ``eps`` as published; ``x`` is ``[T, F]``, ``H_l``
is ``num_attention_heads_per_layer[l]``:

    a    = RMSNorm(x; g1)
    q    = a W_q -> H_l heads of D;  k, v = a W_k, a W_v -> Hkv of D
    gate = sigmoid(a W_og)                       [T, H_l]: a scalar a head
    full layer:    the first R = partial_rotary_factor D features of
                   every head of q and k turn (feature i with i + R/2),
                   the others stay; YaRN frequencies over those R
                   (written out below from the published rule), cos
                   and sin both times attention_factor
    window layer:  all D features turn, plain frequencies of its theta
    key j is visible to query t  iff  j <= t  and
        (full layer  or  j > t - sliding_window)
    o_h  = softmax(q_h k^T / sqrt(D)) v;  o_h <- gate_h o_h
    x'   = x + [o_1 .. o_H] W_o
    b    = RMSNorm(x'; g2)
    dense layer:   y = (silu(b Wg) * (b Wu)) Wd
    sparse layer:  r   = b W_r                   float32
                   ids = the k largest of r (ties to the lower index)
                   w   = softmax(r[ids])
                   y   = scale * sum_{e in ids} w_e SwiGLU_e(b)
                         + SwiGLU_shared(b)
    x''  = x' + y

then the final RMSNorm and an untied head. ``layer_types``,
``mlp_layer_types`` and ``num_attention_heads_per_layer`` are read
each for itself, entry ``l`` for layer ``l``.

What ``config.json`` leaves open, all under ``assumed`` in the
configuration file, each a FIELD there that this file reads (a
correction is a change of data): ``gating: true`` is read as the
sibling ``Laguna-S-2.1``'s ``"per-head"`` (``assumed.gating``); the
routing score is the Qwen-MoE rule the expert keys are named after,
softmax over all renormalised over the chosen, which is the softmax
over the chosen (``assumed.routing``); no gate on the shared expert, no
QK norm, no correction bias; the half-split pairing inside the rotated
features, YaRN's ``dim`` the rotated width, ``truncate`` true
(``assumed.rotary_pairing``); a window that counts the query's own
position (``assumed.window_counts_own``).

**Routing under two precisions** (as ``window_moe_lm``): a bf16
program moves a router logit by a little; where the ``k``-th and the
``k + 1``-th largest lie closer than that the two may choose
differently, and that position's logits part by far more than
rounding. The reference gives every compared position its smallest
**routing margin** over the sparse layers, and :func:`served_gaps`
leaves positions under the configuration's ``tie_margin`` out of the
gap; their share is a check of its own, ``routing_tie_share``. The
reference is never forced onto the program's routes.

Imports nothing of the program. It reads a parameter tree by the zoo's
names (``layer_0.W`` the embedding; ``layer_<i>.mha.Wq``/``Wk``/``Wv``/
``Wo``/``Wog``, ``ln1``/``ln2.gamma``, a dense layer's ``Wg``/``Wu``/
``Wd``, a sparse layer's ``moe.Wr``, ``Weg``/``Weu``/``Wed [E, ...]``,
``Wsg``/``Wsu``/``Wsd``; ``layer_<L+1>.gamma``; ``layer_<L+2>.W``/``b``
the head), which the benchmark made from the seed and may hold in
bf16: every leaf is upcast where it is used, one layer and one expert
at a time.

``precision="fp8"`` is the benchmark's control (see PERF.md): every
matrix product takes operands rounded to float8 e4m3 with a per-tensor
scale. It has to come out as not correct. ``faults`` (names below) are
what the tests inject to show that the comparison sees them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
#: rows of the ``T x T`` scores made at a time
ROW_BLOCK = 512
#: the fullest expert's rows are padded to this times a power of two
#: (one program a size, a handful of sizes, not one a count)
EXPERT_ROWS = 256
#: tie margins whose share and gap a run logs beside the configured
#: one (what the limit and the margin are set from)
LOGGED_MARGINS = (0.0, 0.005, 0.01, 0.02, 0.03, 0.04, 0.05, 0.1)
#: what the tests may inject (each must fail the comparison)
FAULTS = ("no_gate", "gate_from_x", "full_rotary_all",
          "full_plain_freq", "no_attention_factor", "window_full_rule",
          "no_window", "window_off_by_one_page", "no_shared",
          "no_scale", "scale_on_shared", "no_renorm", "sigmoid_scores",
          "drop_route", "bf16_router")


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


def inv_freq(rule: tuple) -> np.ndarray:
    """The ``R / 2`` frequencies of a rule ``(theta, R, yarn, factor)``
    (:func:`layer_rules`), written out from the published YaRN rule
    (``rope_type: "yarn"``): frequency ``i`` of ``theta^(-2i/R)`` is
    kept where it turns more than ``beta_fast`` times within the
    original context, divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and blended by a linear ramp between the two
    dimensions (floor and ceiling: ``truncate``)."""
    theta, dim, yarn, _ = rule
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return (1.0 / base).astype(np.float32)
    factor, original, beta_fast, beta_slow = yarn

    def dim_of(turns):      # the dimension that turns `turns` times
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) / base + ramp / (factor * base)).astype(
        np.float32)


def rope(x, rule: tuple):
    """``x [T, heads, D]`` at positions ``0 .. T-1`` by ``rule``
    ``(theta, R, yarn, factor)``: feature ``i < R/2`` turns with
    feature ``i + R/2``; features from ``R`` on stay as they are."""
    t = x.shape[0]
    half = rule[1] // 2
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq(rule))[None, :])
    cos = (jnp.cos(ang) * rule[3])[:, None, :]
    sin = (jnp.sin(ang) * rule[3])[:, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., 2 * half:]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "rule", "window", "eps", "gate", "precision"))
def attention_half(p, x, *, n_heads, n_kv, rule, window, eps,
                   gate="normed", precision="float32"):
    """The rows after the attention's residual. ``gate``: ``"normed"``
    (the rule), ``"x"`` or ``None`` (faults)."""
    t = x.shape[0]
    mha = p["mha"]
    a = rms_norm(x, p["ln1"]["gamma"], eps)
    q = _mm(a, mha["Wq"], precision).reshape(t, n_heads, -1)
    k = _mm(a, mha["Wk"], precision).reshape(t, n_kv, -1)
    v = _mm(a, mha["Wv"], precision).reshape(t, n_kv, -1)
    q, k = rope(q, rule), rope(k, rule)
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

    o = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(
        t, n_heads, d)
    if gate is not None:
        o = o * jax.nn.sigmoid(_mm(
            a if gate == "normed" else x, mha["Wog"], precision))[
                ..., None]
    return x + _mm(o.reshape(t, -1), mha["Wo"], precision)


def _ranked(x):
    """Indices by falling value, ties to the lower index."""
    return jnp.argsort(-x, axis=-1, stable=True)


@functools.partial(jax.jit, static_argnames=("top_k", "score", "drop"))
def route(r, *, top_k, score="softmax_topk", drop=False):
    """Router logits ``r [T, E]`` to ``(ids [T, k], weights [T, k],
    margin [T])``: the ``k`` largest, weighed by the softmax over them
    (``score="softmax_topk"``); ``margin`` the ``k``-th largest less
    the next, in logits. Faults: ``"softmax_all"`` weighs by the
    softmax over all ``E`` without renormalising, ``"sigmoid"`` by the
    chosen logits' sigmoids normalised to sum 1."""
    rank = _ranked(r)
    ids = rank[:, :top_k]
    top = jnp.take_along_axis(r, rank[:, :top_k + 1], axis=1)
    if score == "softmax_topk":
        w = jax.nn.softmax(top[:, :top_k], axis=-1)
    elif score == "softmax_all":
        w = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), ids, axis=1)
    else:
        w = jax.nn.sigmoid(top[:, :top_k])
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    if drop:    # a fault: each token's last chosen expert left out
        w = w.at[:, -1].set(0.0)
    return ids, w, top[:, top_k - 1] - top[:, top_k]


@functools.partial(jax.jit, static_argnames=("eps",))
def second_norm(p, x, *, eps):
    return rms_norm(x, p["ln2"]["gamma"], eps)


def swiglu(rows, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(rows, wg, precision))
               * _mm(rows, wu, precision), wd, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def unit_rows(b, wg, wu, wd, *, precision="float32"):
    """One gated unit over every row: the dense layer's, the shared
    expert's."""
    return swiglu(b, wg, wu, wd, precision)


@functools.partial(jax.jit, static_argnames=("cap", "precision"))
def routed_rows(b, chose, weight, weg, weu, wed, *, cap,
                precision="float32"):
    """The routed experts' part of a layer, ``[T, F]``: one expert
    after another (a scan over the stacked experts, each upcast where
    it is used), expert ``e`` applied to the rows that chose it
    (``chose [T, E]``; ``cap`` of them at most, a static size) and
    its outputs weighed by ``weight [T, E]`` and added to their rows."""
    t = b.shape[0]
    padded = jnp.concatenate([b, jnp.zeros_like(b[:1])])

    def one(y, expert):
        chose_e, w, wg, wu, wd = expert
        (idx,) = jnp.nonzero(chose_e, size=cap, fill_value=t)
        out = swiglu(padded[idx], wg, wu, wd, precision)
        w = jnp.concatenate([w, jnp.zeros_like(w[:1])])[idx]
        return y.at[idx].add(w[:, None] * out, mode="drop"), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(b),
                        (chose.T, weight.T, weg, weu, wed))
    return y


def experts_half(p, x, n_real, d, precision, faults):
    """``(x'', margin [T])``: a sparse layer's feed-forward over the
    rows after the attention. Rows at and past ``n_real`` (right
    padding, never read by a real row) choose no expert."""
    b = second_norm(p, x, eps=d["eps"])
    moe = p["moe"]
    r = _mm(b, moe["Wr"], precision)
    if "bf16_router" in faults:
        r = r.astype(jnp.bfloat16).astype(jnp.float32)
    score = ("softmax_all" if "no_renorm" in faults else
             "sigmoid" if "sigmoid_scores" in faults else d["score"])
    ids, w, margin = route(r, top_k=d["top_k"], score=score,
                           drop="drop_route" in faults)
    real = jnp.arange(x.shape[0]) < n_real
    n_e = moe["Weg"].shape[0]
    chose = (ids[:, :, None] == jnp.arange(n_e)) & real[:, None, None]
    w_e = jnp.sum(w[:, :, None] * chose, axis=1)            # [T, E]
    chose = jnp.any(chose, axis=1)                           # [T, E]
    cap = EXPERT_ROWS       # the fullest expert's rows, a power of two
    while cap < int(jnp.max(jnp.sum(chose, axis=0))):
        cap *= 2
    scale = 1.0 if "no_scale" in faults else d["scale"]
    y = scale * routed_rows(b, chose, w_e, moe["Weg"], moe["Weu"],
                            moe["Wed"], cap=cap, precision=precision)
    if "no_shared" not in faults:
        shared = unit_rows(b, moe["Wsg"], moe["Wsu"], moe["Wsd"],
                           precision=precision)
        y = y + (scale * shared if "scale_on_shared" in faults
                 else shared)
    return x + y, margin


def dense_half(p, x, d, precision):
    b = second_norm(p, x, eps=d["eps"])
    return x + unit_rows(b, p["Wg"], p["Wu"], p["Wd"],
                         precision=precision)


@functools.partial(jax.jit, static_argnames=("rows", "eps", "precision"))
def head(norm, out, x, start, *, rows, eps, precision="float32"):
    x = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    x = rms_norm(x, norm["gamma"], eps)
    return _mm(x, out["W"], precision) + out["b"].astype(jnp.float32)


def layer_rules(d: dict, li: int, faults=()):
    """``(rule, window or None)`` of layer ``li``; ``rule`` is
    ``(theta, rotated width, yarn or None, factor)``."""
    windowed = d["kinds"][li] == "window"
    rule = d["rules"]["window" if windowed else "full"]
    if windowed and "window_full_rule" in faults:
        rule = d["rules"]["full"]
    if not windowed:
        theta, dim, yarn, factor = rule
        if "full_rotary_all" in faults:
            dim = d["head_dim"]
        if "full_plain_freq" in faults:
            yarn = None
        if "no_attention_factor" in faults:
            factor = 1.0
        rule = (theta, dim, yarn, factor)
    window = d["window"] if windowed else None
    if window is not None and "no_window" in faults:
        window = None
    if window is not None and "window_off_by_one_page" in faults:
        window = window - 16
    return rule, window


def logits_from(params, tokens, start, *, d, rows, n_real=None,
                precision="float32", faults=()):
    """Next-token logits ``[rows, V]`` at positions ``start .. start +
    rows - 1`` of one sequence ``tokens [T]`` after a full causal
    forward, and those positions' smallest routing margin over the
    sparse layers. Each layer's halves are programs of their own, so
    one layer's float32 copies are all that lie beside the weights."""
    n_layers = d["n_layers"]
    n_real = tokens.shape[0] if n_real is None else n_real
    x = params["layer_0"]["W"][tokens].astype(jnp.float32)
    margin = jnp.full((tokens.shape[0],), jnp.inf)
    gate = (None if "no_gate" in faults or not d["gated"]
            else "x" if "gate_from_x" in faults else "normed")
    for i in range(n_layers):
        p = params[f"layer_{i + 1}"]
        rule, window = layer_rules(d, i, faults)
        x = attention_half(
            p, x, n_heads=d["heads"][i], n_kv=d["n_kv"], rule=rule,
            window=window, eps=d["eps"], gate=gate, precision=precision)
        if d["sparse"][i]:
            x, m = experts_half(p, x, n_real, d, precision, faults)
            margin = jnp.minimum(margin, m)
        else:
            x = dense_half(p, x, d, precision)
    logits = head(params[f"layer_{n_layers + 1}"],
                  params[f"layer_{n_layers + 2}"], x, start, rows=rows,
                  eps=d["eps"], precision=precision)
    return logits, jax.lax.dynamic_slice_in_dim(margin, start, rows)


def rule_of(published: dict, head_dim: int) -> tuple:
    """One kind's entry of ``rope_parameters`` as ``(theta, rotated
    width, yarn or None, factor)``."""
    kind = published["rope_type"]
    if kind not in ("default", "yarn"):
        raise ValueError(f"rope_type {kind!r}")
    yarn = None if kind == "default" else (
        float(published["factor"]),
        float(published["original_max_position_embeddings"]),
        float(published["beta_fast"]), float(published["beta_slow"]))
    return (float(published["rope_theta"]),
            int(round(published["partial_rotary_factor"] * head_dim)),
            yarn, float(published.get("attention_factor", 1.0)))


def dims(config: dict) -> dict:
    """The reference's sizes: the published configuration's keys and
    the ``assumed`` fields that settle what they leave open."""
    n = config["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(config[key]) < n:
            raise ValueError(f"{key} names {len(config[key])} layers "
                             f"of {n}")
    assumed = config["assumed"]
    refused = {
        "an attention bias": config["attention_bias"],
        "a tied head": config["tie_word_embeddings"],
        "router weights on the experts' input":
            config["moe_apply_router_weight_on_input"],
        f"gating {assumed['gating']!r}":
            config["gating"] and assumed["gating"] != "per-head",
        f"routing {assumed['routing']!r}":
            assumed["routing"] != "softmax_topk",
        f"rotary pairing {assumed['rotary_pairing']!r}":
            assumed["rotary_pairing"] != "half-split",
        "a window that leaves the query's own position out":
            not assumed["window_counts_own"],
    }
    for what, found in refused.items():
        if found:
            raise ValueError(f"the reference does not compute {what}")
    kinds = {"full_attention": "full", "sliding_attention": "window"}
    hd = config["head_dim"]
    return dict(
        n_layers=n, n_kv=config["num_key_value_heads"], head_dim=hd,
        heads=tuple(config["num_attention_heads_per_layer"][:n]),
        kinds=tuple(kinds[k] for k in config["layer_types"][:n]),
        sparse=tuple(k == "sparse"
                     for k in config["mlp_layer_types"][:n]),
        rules={kinds[k]: rule_of(v, hd)
               for k, v in config["rope_parameters"].items()
               if k in kinds},
        window=config["sliding_window"], gated=bool(config["gating"]),
        eps=float(config["rms_norm_eps"]),
        top_k=config["num_experts_per_tok"], score=assumed["routing"],
        scale=float(config["moe_routed_scaling_factor"]))


#: lengths a sequence is padded to, and rows of logits made, below the
#: caller's own ``pad_to`` and ``rows`` (one program a size)
PAD_TO = (2048, 4096, 8192)
ROWS = (1024,)


def padded_sizes(n_seq: int, t0: int, n_out: int, pad_to: int,
                 rows: int):
    """``(pad_to, rows)`` no larger than the caller's: the fewest rows
    of logits of :data:`ROWS` that hold the ``n_out`` compared
    positions, and the shortest length of :data:`PAD_TO` that holds
    the sequence and those rows. The forward is causal and the
    padding is never read by a real row, so the logits are the same;
    the scores of a sequence padded to 4,096 positions are an eighth
    of those of 11,264."""
    fewer = next((r for r in ROWS if n_out <= r < rows), rows)
    need = max(n_seq, t0 - 1 + fewer)
    if need > pad_to:
        return pad_to, rows
    return next((t for t in PAD_TO if need <= t < pad_to), pad_to), fewer


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
    pad_to, rows = padded_sizes(len(seq), t0, n_out, pad_to, rows)
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

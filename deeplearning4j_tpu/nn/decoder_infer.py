"""The decoder block at inference: ONE block, ONE stack loop.

:func:`block` is what a pre-norm decoder block computes when it serves,
:func:`stack` the loop around it, :func:`logits` the final norm and LM
head. ``zoo/gpt.py`` (``generate``, beam search, dense prefill) and
``serving/scheduler.py`` (every program of the gateway) run these and
supply the ONE thing they differ in, a **cache object**:
``attend(li, mha, h) -> a`` projects the normed rows ``h`` with layer
``li``'s mixer parameters, writes what the rows add to the cache,
reads it, and returns the mixer's output flattened over heads. It is
built inside the traced program, holds the arrays it rewrites while
the program is traced, and hands them back afterwards. The model's
mixer is chosen where the cache object is built, never in the block:
a hybrid decoder, whose layers differ in kind, gets ONE cache object
that goes by each layer's kind (:class:`ByKind`).
The dense layouts' cache objects live here, the paged pool's with the
pager (``serving/kv_pager.py``). ``dims`` is anything with
``n_layers``, ``n_heads`` (with ``heads_by_layer`` a count a LAYER:
:func:`layer_heads`), ``n_kv_heads``, ``rope_theta`` (None: no
positional term; with ``rope_layers`` the layers that rotate, the
others carrying no positions; with ``rope_by_kind`` an
``ops.rotary.RopeRule`` a KIND of softmax layer, base, rotated width,
frequencies and factor: :func:`layer_theta`), ``windowed`` (a
:class:`WindowSpec`: the layers whose keys a sliding window bounds,
:func:`layer_window`) and ``tie_embeddings``: the zoo model itself (a
latent mixer's sizes are its ``latent``, its expert layers' its
``experts``, a hybrid's kinds and Mamba sizes its ``hybrid``). What a
published decoder multiplies by is read from ``dims`` too, each where
``dims`` has it and it is not None (:func:`scalar`):
``embedding_multiplier``, ``residual_multiplier``, ``logits_scaling``,
``attention_multiplier`` (the scores' scale in ``d^-1/2``'s place) and
``norm_eps``; a decoder without one holds no multiply for it, so its
programs lower to what they were. ARCHITECTURE.md §15 has the picture,
and why the training block stays apart.

The feed-forward is chosen by what a block's parameters HOLD, never by
a flag: ``Wg``/``Wu``/``Wd`` a dense SwiGLU, ``moe`` the expert layer
of ``ops/moe.py`` (this chip's experts beside the shared one). So is
the attention's output gate: a mixer that holds ``Wog [F, H]`` has its
heads' outputs multiplied by ``sigmoid(h1 Wog)``, a scalar a head, in
front of ``Wo`` (:func:`head_gate`; the retention mixer's ``Wgate`` is
its decay's, another thing).

A decoder with ONE head count, ONE plain rotary rule and no gate runs
none of what serves the others: its programs lower to the text they
had before a second kind of softmax layer could differ from the first
in anything but its window (``tests/test_serving_programs_pinned.py``).

Imports ``ops/`` and ``nn/layers/``, never ``zoo/`` or ``serving/``.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.attention import (
    _use_flash, latent_attention_expanded, rotary_embedding, rotary_tables,
    rotary_turn, scaled_dot_attention)
from deeplearning4j_tpu.nn.layers.core import RMSNORM_EPS
from deeplearning4j_tpu.obs import devtime
from deeplearning4j_tpu.ops import fused_norms, latent, moe, retention, ssm
from deeplearning4j_tpu.ops.rotary import RopeRule


def rms(x, gamma, eps=RMSNORM_EPS):
    """RMSNorm over the trailing axis, platform-helper dispatched
    (ops/fused_norms.py): fused Pallas kernel on TPU, else plain XLA."""
    return fused_norms.rms_norm(x, gamma, eps=eps)


def quant_kv(kvr, channel_axis: int):
    """int8 KV quantisation shared by every cache layout: per-slice
    abs-max scales over ``channel_axis`` (the D channels of each k/v
    half), round-to-int8 codes. Returns (codes int8, scales f32 with
    the channel axis dropped)."""
    kvr = kvr.astype(jnp.float32)
    s = jnp.maximum(
        jnp.max(jnp.abs(kvr), axis=channel_axis) / 127.0, 1e-8)
    w8 = jnp.round(kvr / jnp.expand_dims(s, channel_axis)).astype(
        jnp.int8)
    return w8, s.astype(jnp.float32)


def rotary_rows(x, theta: float, pos):
    """RoPE at one position PER ROW (a continuous batch): ``x``
    [N, H, D], ``pos`` [N] i32. Bit-identical per row to
    ``rotary_embedding(x[:, None], offset=pos_scalar)[:, 0]`` (same
    f32 angle math, same half-split pairing). Folding the two into one
    helper changes what the TPU compiler makes of the decode step
    (PERF.md §6, PR 28), so there are two. ``theta=None``: no
    positional term, ``x`` as it is; a :class:`RopeRule` in its place
    turns by the rule (a program with several layers of one rule
    makes its cos and sin once: :class:`Turns`)."""
    if theta is None:
        return x
    if isinstance(theta, RopeRule):
        return Turns(per_row=True)(x, theta, pos)
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]  # [N, D/2]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


class Turns:
    """The rotations of ONE traced program: ``turns(x, rule, pos)``
    rotates ``x`` by a layer's ``rule`` (:func:`layer_theta`) at the
    program's positions, the same at every call: ``pos [N]`` one a row
    of ``x [N, H, D]`` (``per_row``: a continuous batch) or the scalar
    offset of ``x [B, T, H, D]``'s first position. A plain base or
    None goes to :func:`rotary_rows` / ``rotary_embedding`` as it
    always has; a :class:`RopeRule`'s cos and sin are made ONCE a
    program from the positions, in float32, however many layers turn
    by it, under the ``attn.rotary`` scope with the turns themselves."""

    def __init__(self, per_row: bool):
        self.per_row = per_row
        self._tables = {}

    def __call__(self, x, rule, pos=0):
        if not isinstance(rule, RopeRule):
            return (rotary_rows(x, rule, pos) if self.per_row
                    else rotary_embedding(x, rule, offset=pos))
        with devtime.scope("attn.rotary"):
            key = (rule, x.shape[-1])
            if key not in self._tables:
                if not self.per_row:
                    pos = pos + jnp.arange(x.shape[1], dtype=jnp.float32)
                self._tables[key] = rotary_tables(
                    pos, rule.inv_freq(x.shape[-1]), rule.factor)
            cos, sin = self._tables[key]
            if self.per_row:
                return rotary_turn(x, cos[:, None, :], sin[:, None, :])
            return rotary_turn(x, cos[None, :, None, :],
                               sin[None, :, None, :])


@dataclasses.dataclass(frozen=True)
class WindowSpec:
    """A decoder whose softmax layers are of two KINDS: a ``"full"``
    layer's query sees every earlier key, a ``"window"`` layer's the
    last ``window`` keys only, its own included (key ``j`` is visible
    to query ``t`` iff ``t - window < j <= t``). One kind a layer; the
    kinds differ in what a cache must keep (:class:`ByKind`) and, where
    ``dims`` says so, in their head count (:func:`layer_heads`) and
    their rotary rule (:func:`layer_theta`); the KV heads and a head's
    width are the model's, so both kinds' pages are one shape."""
    window: int
    kinds: Tuple[str, ...]

    KINDS = ("full", "window")

    def __post_init__(self):
        object.__setattr__(self, "kinds", tuple(self.kinds))
        bad = sorted(set(self.kinds) - set(self.KINDS))
        if bad or self.window < 1:
            raise ValueError(f"window={self.window}, layer kinds {bad} "
                             f"({' | '.join(self.KINDS)})")

    def layers(self, kind: str) -> Tuple[int, ...]:
        """The model's layers of ``kind``, in order."""
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    def index(self, li: int) -> int:
        """Layer ``li``'s place among the layers of its own kind."""
        return self.kinds[:li].count(self.kinds[li])

    def to_dict(self) -> dict:
        return {"window": self.window, "kinds": list(self.kinds)}

    @classmethod
    def of(cls, value) -> "WindowSpec":
        return value if isinstance(value, cls) else cls(**dict(value))


def layer_theta(dims, li: int):
    """Layer ``li``'s rotary rule: the base ``dims.rope_theta``, or
    None (no positional term) where ``dims.rope_layers`` names the
    rotated layers and ``li`` is not among them; where
    ``dims.rope_by_kind`` gives the kinds of a windowed decoder a rule
    each, the :class:`RopeRule` (or None) of the layer's kind."""
    by_kind = getattr(dims, "rope_by_kind", None)
    if by_kind is not None:
        return by_kind[dims.windowed.kinds[li]]
    layers = getattr(dims, "rope_layers", None)
    return dims.rope_theta if layers is None or li in layers else None


def layer_heads(dims, li=None) -> int:
    """Layer ``li``'s query heads: ``dims.heads_by_layer[li]`` where
    the layers differ, else ``dims.n_heads``."""
    by_layer = getattr(dims, "heads_by_layer", None)
    return dims.n_heads if by_layer is None or li is None else by_layer[li]


def layer_window(dims, li: int):
    """The sliding window of layer ``li``'s keys, or None (every
    earlier key is visible)."""
    spec = getattr(dims, "windowed", None)
    return (spec.window if spec is not None
            and spec.kinds[li] == "window" else None)


def scalar(dims, name: str):
    """``dims``' published scalar ``name``, or None (``dims`` is
    anything with the decoder's sizes: most have no such scalar)."""
    return getattr(dims, name, None)


def q_fold(dims, head_dim: int):
    """What a query is multiplied by so that the attention's own
    ``d^-1/2`` gives ``dims.attention_multiplier`` (1/8 for 64-wide
    heads at 1/64: a power of two, exact in any float); None where
    ``dims`` has no such scale."""
    scale = scalar(dims, "attention_multiplier")
    return None if scale is None else float(scale) * head_dim ** 0.5


def qkv(mha, h, dims, rotate, li=None):
    """The softmax mixer's operands from normed rows ``h [..., F]``:
    ``q [..., H, D]`` (``H`` layer ``li``'s: :func:`layer_heads`), ``k
    [..., Hkv, D]`` (both rotated), ``v`` (``ops.retention.project``
    is the retention mixer's). A published score scale is folded into
    the query, so that every attention behind this keeps its own
    ``d^-1/2``."""
    lead = h.shape[:-1]
    q = (h @ mha["Wq"]).reshape(*lead, layer_heads(dims, li), -1)
    q = _times(q, q_fold(dims, q.shape[-1]))
    k = (h @ mha["Wk"]).reshape(*lead, dims.n_kv_heads, -1)
    v = (h @ mha["Wv"]).reshape(*lead, dims.n_kv_heads, -1)
    return rotate(q), rotate(k), v


def ffn(pblk, h, experts=None, live=None, route_rows=None):
    """The feed-forward of normed rows ``h``, by what the block holds:
    ``(y, counts)``, ``counts`` an expert layer's held experts' pairs
    (``ops.moe.layer``; ``experts`` its ``ExpertSpec``, ``live`` the
    rows that carry a token, ``route_rows`` the rows its router reads
    where they are not ``h``), None for a dense SwiGLU."""
    if "moe" in pblk:
        return moe.layer(pblk["moe"], h, experts, live=live,
                         route_rows=route_rows)
    h = jax.nn.silu(h @ pblk["Wg"]) * (h @ pblk["Wu"])
    return h @ pblk["Wd"], None


def head_gate(a, h, w_gate):
    """The mixer's output ``a [..., H D]`` with each head's ``D``
    values times ``sigmoid(h W_gate)``'s scalar of that head (``h`` the
    normed rows the mixer read, ``w_gate [F, H]``): the logits and the
    sigmoid in float32 on ``[..., H]``, the product in ``a``'s dtype,
    so that nothing ``[..., H D]`` wide is made in float32."""
    with devtime.scope("attn.gate"):
        g = jax.nn.sigmoid(jnp.dot(
            h, w_gate, preferred_element_type=jnp.float32)).astype(a.dtype)
        return (a.reshape(*g.shape, -1) * g[..., None]).reshape(a.shape)


def _times(v, by):
    """``v`` times a published scalar; ``v`` itself, and no multiply
    in the program, where the scalar is None."""
    return v if by is None else v * jnp.asarray(by, v.dtype)


def block(pblk, x, attend, li: int, experts=None, counts=None,
          live=None, scope: str = "block", residual=None,
          eps: float = RMSNORM_EPS):
    """One decoder block over rows ``x [..., F]``: ``ln1`` → mixer →
    ``Wo`` + residual → ``ln2`` → feed-forward → residual. An expert
    layer's counts are appended to ``counts`` where a list is given;
    it routes the ``live`` rows only (:func:`ffn`). The block's two
    halves carry a devtime scope each, ``{scope}.mixer`` and
    ``{scope}.ffn`` (HLO metadata only), so that a dense model's
    device time splits the way an expert or retention model's does
    through its ``ops.*`` scopes. ``residual`` multiplies each half's
    addition to the residual stream (a published
    ``residual_multiplier``; None: no multiply); ``eps`` is the two
    norms'. An expert layer whose router sits BEFORE the mixer
    (``experts.route_before_mixer``) routes by the pre-attention
    normed rows. A mixer that holds ``Wog`` has its output gated a
    head (:func:`head_gate`)."""
    mha = pblk["mha"]
    with devtime.scope(f"{scope}.mixer"):
        h1 = rms(x, pblk["ln1"]["gamma"], eps)
        a = attend(li, mha, h1)
        if "Wog" in mha:
            a = head_gate(a, h1, mha["Wog"])
        x = x + _times(a @ mha["Wo"], residual)
        if "bo" in mha:
            x = x + _times(mha["bo"], residual)
    with devtime.scope(f"{scope}.ffn"):
        y, pairs = ffn(pblk, rms(x, pblk["ln2"]["gamma"], eps),
                       experts, live,
                       h1 if "moe" in pblk
                       and experts.route_before_mixer else None)
        if pairs is not None and counts is not None:
            counts.append(pairs)
        return x + _times(y, residual)


def stack(params, toks, dims, attend, scope: str,
          block_scope: str = "", counts=None, live=None):
    """Token ids ``toks`` (any shape) through the embedding and every
    block, to the rows before the final norm. The devtime scopes are
    HLO metadata only: block i's device time gets the name
    ``{block_scope or scope}.block_{i}``, its halves
    ``….block_{i}.mixer`` and ``….block_{i}.ffn``. A caller that
    wants the expert layers' pair counts passes a list as ``counts``:
    each expert layer appends its ``[n_held]``. ``live`` (bool,
    ``toks``' shape) marks the rows that carry a token; the expert
    layers route no other (a bucket's padding, a slot without a
    sequence)."""
    with devtime.scope(f"{scope}.embed"):
        x = _times(params["layer_0"]["W"][toks],
                   scalar(dims, "embedding_multiplier"))
    experts = getattr(dims, "experts", None)
    residual = scalar(dims, "residual_multiplier")
    eps = scalar(dims, "norm_eps") or RMSNORM_EPS
    for i in range(dims.n_layers):
        name = f"{block_scope or scope}.block_{i}"
        with devtime.scope(name):
            x = block(params[f"layer_{i + 1}"], x, attend, i, experts,
                      counts, live, name, residual=residual, eps=eps)
    return x


def logits(params, x, dims, scope: str):
    """Final norm and LM head over the rows whose logits are wanted
    (never a whole prompt's). A tied head is the embedding matrix
    read transposed in the dot: nothing is materialised."""
    scaling = scalar(dims, "logits_scaling")
    with devtime.scope(f"{scope}.lm_head"):
        x = rms(x, params[f"layer_{dims.n_layers + 1}"]["gamma"],
                scalar(dims, "norm_eps") or RMSNORM_EPS)
        head = params[f"layer_{dims.n_layers + 2}"]
        hw = (params["layer_0"]["W"].T if dims.tie_embeddings
              else head["W"])
        # on the normed rows, where the training graph's final norm
        # has it (1/8 is exact either side of the product)
        x = _times(x, None if scaling is None else 1.0 / scaling)
        return x @ hw + head["b"]


def filter_logits(logits, top_k, top_p, nucleus):
    """Top-k then nucleus filtering on [B, V] f32 logits (filtered
    entries → -inf). ``top_k``/``nucleus`` are static, so unused
    filters cost nothing (plain temperature sampling never sorts);
    ``top_p`` is a traced scalar. One descending sort serves both
    filters."""
    if not (top_k is not None or nucleus):
        return logits
    if top_k is not None and not nucleus:
        # top-k alone never needs the full-vocab sort: lax.top_k is
        # the cheap per-token idiom (VERDICT r3 Weak #4)
        kth = jax.lax.top_k(logits, top_k)[0][:, -1]
        return jnp.where(logits < kth[:, None], -jnp.inf, logits)
    sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k is not None:
        logits = jnp.where(
            logits < sorted_l[:, top_k - 1][:, None], -jnp.inf,
            logits)
        sorted_l = jnp.where(
            jnp.arange(sorted_l.shape[-1])[None, :] < top_k,
            sorted_l, -jnp.inf)
    if nucleus:
        # keep the smallest prefix of the sorted distribution whose
        # cumulative mass reaches top_p (always keep the argmax)
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = jnp.concatenate(
            [jnp.ones_like(cum[:, :1], bool),
             cum[:, :-1] < top_p], axis=-1)
        # threshold logit = smallest kept sorted logit per row
        thresh = jnp.min(
            jnp.where(keep_sorted, sorted_l, jnp.inf),
            axis=-1, keepdims=True)
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return logits


def pick(logits, temperature, top_p, key, *, sample, top_k, nucleus):
    """Next-token choice from [rows, V] logits: argmax, or a filtered
    categorical sample."""
    if sample:
        lf = filter_logits(logits.astype(jnp.float32) / temperature,
                           top_k, top_p, nucleus)
        return jax.random.categorical(key, lf, axis=-1).astype(
            jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# -- a cache object a layer KIND ----------------------------------------------

class Attend:
    """A bare ``attend`` function as a cache object."""

    def __init__(self, attend):
        self.attend = attend


class ByKind:
    """The cache object of a decoder whose layers differ in KIND (a
    hybrid's ``softmax=`` and ``mamba2=``; a windowed decoder's
    ``full=`` and ``window=``): layer ``li``'s rows go to the cache
    object of the layer's kind, under the layer's index among the
    layers of that kind, since each kind's caches (a list of dense
    arrays, a pool of pages) are stacked over its own layers only.
    ``caches`` and ``pool`` hand back what the kinds' objects hold, in
    the order the kinds were given (the order of the pager's pool
    tuple). A kind's object that must know the MODEL's layer (a
    rotation that differs by layer) is built with the layers of its
    kind (``spec.layers(kind)``)."""

    def __init__(self, spec, **by_kind):
        self.spec = spec
        self.by = by_kind

    def attend(self, li, mha, h):
        return self.by[self.spec.kinds[li]].attend(
            self.spec.index(li), mha, h)

    @property
    def caches(self):
        return tuple(c.caches for c in self.by.values())

    @property
    def pool(self):
        return sum((tuple(c.pool) for c in self.by.values()), ())


# -- the dense cache objects -------------------------------------------------

class _AtPosition:
    """Every row at ONE position ``pos`` (traced) of ``generate()``'s
    and beam search's dense caches; ``caches`` holds the layers'
    arrays as the rows left them."""

    def __init__(self, dims, caches, pos):
        self.dims = dims
        self.caches = list(caches)
        self.pos = pos
        self.turns = Turns(per_row=False)

    def rotate(self, z, li=None):       # [rows, heads, d]
        theta = (self.dims.rope_theta if li is None
                 else layer_theta(self.dims, li))
        return self.turns(z[:, None], theta, self.pos)[:, 0]


class DenseKV(_AtPosition):
    """Softmax attention against ONE ``[rows, Hkv, 2D, T]`` array a
    layer (k rows 0:D, v rows D:2D), or under ``cache_quant="int8"``
    its codes beside ``[rows, Hkv, 2, T]`` f32 scales. The minor
    (2D, T) dims tile the TPU's (8, 128) layout exactly (the natural
    [rows, T, Hkv, D] pads (12, 64) tiles to (16, 128), 2.67x the
    bytes), and ONE fused dynamic-update a layer instead of two
    halves the per-step update overhead (~85 µs an op at B=32)."""

    def attend(self, li, mha, h):
        dims, pos = self.dims, self.pos
        rows, dt = h.shape[0], h.dtype
        q, k, v = qkv(mha, h, dims, lambda z: self.rotate(z, li), li)
        n_kv, hd = k.shape[1:]
        kv = jnp.concatenate([k, v], axis=2)        # [rows, Kv, 2D]
        ckv = self.caches[li]
        if isinstance(ckv, tuple):
            # int8 cache: quantise this position's kv against fresh
            # per-(row, head, half) scales, update codes + scales
            w8, sc = ckv
            q8, s_new = quant_kv(kv.reshape(rows, n_kv, 2, hd), 3)
            w8 = jax.lax.dynamic_update_index_in_dim(
                w8, q8.reshape(rows, n_kv, 2 * hd), pos, 3)
            sc = jax.lax.dynamic_update_index_in_dim(sc, s_new, pos, 3)
            self.caches[li] = (w8, sc)
            # scales are constant over the channel axis, so they
            # factor OUT of both einsums: the dots read PURE int8 (the
            # astype fuses into the operand read, half the cache
            # bytes; a mixed int8×bf16 dot_general was also measured
            # and is slightly slower), k-scales multiply the [.., T]
            # scores after the dot, v-scales pre-scale the softmax
            # weights. The scales STAY f32: the scale-multiplies
            # upcast and only their result casts back to the compute
            # dtype, so bf16 rounding hits each value once, not twice
            # (scale bytes are 4/head_dim of the cache read)
            ck = w8[:, :, :hd, :].astype(dt)
            cv = w8[:, :, hd:, :].astype(dt)
            k_scale = sc[:, :, 0, None, :]
            v_scale = sc[:, :, 1, None, :]
        else:
            ckv = jax.lax.dynamic_update_index_in_dim(ckv, kv, pos, 3)
            self.caches[li] = ckv
            ck, cv = ckv[:, :, :hd, :], ckv[:, :, hd:, :]
            k_scale = v_scale = None
        # grouped einsums attend straight against the SMALL cache
        # (GQA's cache-bandwidth saving survives decode: no
        # [rows, total, H, hd] broadcast is ever materialised)
        qg = q.reshape(rows, n_kv, q.shape[1] // n_kv, hd)
        s = jnp.einsum("bkgd,bkdt->bkgt", qg, ck) / jnp.sqrt(
            jnp.asarray(hd, dt))
        if k_scale is not None:
            s = (s * k_scale).astype(dt)
        live = jnp.arange(ck.shape[3])[None, None, None, :] <= pos
        window = layer_window(dims, li)
        if window is not None:      # the dense cache keeps every row
            live = live & (jnp.arange(ck.shape[3])[None, None, None, :]
                           > pos - window)
        w = jax.nn.softmax(jnp.where(live, s, -1e9), axis=-1)
        if v_scale is not None:
            w = (w * v_scale).astype(dt)
        return jnp.einsum("bkgt,bkdt->bkgd", w, cv).reshape(rows, -1)


def dense_kv(k, v, cache_len: int, quant: bool):
    """A prefilled layer's ``k, v [B, Tb, Hkv, D]`` in :class:`DenseKV`'s
    layout, padded to ``cache_len`` positions (one relayout transpose
    at prefill, none on any decode step's read)."""
    bsz, tb, n_kv, hd = k.shape
    pad = ((0, 0), (0, 0), (0, 0), (0, cache_len - tb))
    kv = jnp.concatenate([k.transpose(0, 2, 3, 1),
                          v.transpose(0, 2, 3, 1)], axis=2)
    if not quant:
        return jnp.pad(kv, pad)
    w8, s = quant_kv(kv.reshape(bsz, n_kv, 2, hd, tb), 3)
    return (jnp.pad(w8.reshape(bsz, n_kv, 2 * hd, tb), pad),
            jnp.pad(s, pad))


def causal_prefill(dims, keep, lengths=None):
    """The ``attend`` of a whole padded prompt ``h [B, Tb, F]``: causal
    attention through ``scaled_dot_attention`` (flash-dispatched: long
    prompts take the Pallas O(T)-memory path on TPU), each layer's
    rotated keys and its values handed to ``keep(li, k, v)``, which
    lays them out as the decode steps will read them (:func:`dense_kv`,
    or the pager's pages). Rows past the prompt's end (``lengths``
    int32 ``[B]``, where the caller knows it: the flash kernel then
    spends nothing on them) hold padding junk, finite: causality keeps
    it out of every real row's context, and decode overwrites row
    ``p`` before attending at ``p``. A layer
    rotates, and bounds its keys by a window, as ``dims`` says of it
    (:func:`layer_theta`, :func:`layer_window`)."""
    turns = Turns(per_row=False)

    def attend(li, mha, h):
        q, k, v = qkv(mha, h, dims,
                      lambda z: turns(z, layer_theta(dims, li)), li)
        keep(li, k, v)
        # the lengths go along where the kernel that reads them takes
        # the call (the einsum has no use for them)
        live = ({"lengths": lengths} if lengths is not None
                and _use_flash(q, k, True) else {})
        spec = getattr(dims, "windowed", None)
        if spec is None:
            return scaled_dot_attention(
                q, k, v, causal=True, **live).reshape(*h.shape[:-1], -1)
        # a scope of the layer's kind, as the step's page walks have
        with devtime.scope(f"attn.{spec.kinds[li]}"):
            return scaled_dot_attention(
                q, k, v, causal=True, window=layer_window(dims, li),
                **live).reshape(*h.shape[:-1], -1)
    return attend


class DenseLatent(_AtPosition):
    """Latent attention against ONE ``[rows, T, kv_rank + rope]`` array
    a layer: the position's latent row is written, and the absorbed
    form reads the rows up to it (``ops/latent.py``)."""

    def attend(self, li, mha, h):
        dims, pos = self.dims, self.pos
        spec = dims.latent
        rows = h.shape[0]
        q_nope, q_rope, row = latent.project(
            mha, h, spec, dims.n_heads, dims.rope_theta,
            jnp.full((rows,), pos))
        cache = jax.lax.dynamic_update_index_in_dim(
            self.caches[li], row.astype(self.caches[li].dtype), pos, 1)
        self.caches[li] = cache
        o = latent.attend_rows(
            latent.absorb(mha, q_nope, q_rope, spec), cache,
            jnp.full((rows,), pos + 1), latent.softmax_scale(spec),
            spec.kv_rank)
        return latent.unabsorb(mha, o, spec)


def latent_prefill(dims, keep, lengths=None):
    """The ``attend`` of a whole padded prompt ``h [B, Tb, F]`` under
    latent attention: the EXPANDED form
    (``latent_attention_expanded``: K and V of every position from its
    latent, flash-dispatched as :func:`causal_prefill`'s), each
    layer's latent rows ``[B, Tb, kv_rank + rope]`` handed to
    ``keep(li, rows)``. Padding rows, and ``lengths``, as in
    :func:`causal_prefill`."""
    def attend(li, mha, h):
        with devtime.scope("ops.latent_prefill"):
            a, rows = latent_attention_expanded(
                mha, h, dims.latent, dims.n_heads, dims.rope_theta,
                lengths)
        keep(li, rows)
        return a
    return attend


class DenseState(_AtPosition):
    """Power retention: a layer's "cache" is its recurrent state
    ``(S [rows, Hkv, rows_of_d, d], Z [rows, Hkv, d, d])``, updated
    once a position by the recurrence."""

    def attend(self, li, mha, h):
        dims = self.dims
        q, k, v, log_g = retention.project(
            mha, h, dims.n_heads, dims.n_kv_heads, self.rotate,
            RMSNORM_EPS)
        a, self.caches[li] = retention.retention_step(
            q, k, v, log_g, self.caches[li])
        return a.reshape(h.shape[0], -1)


class RetentionRows:
    """Power retention over a chunk of rows a sequence, by the chunked
    form: ``h`` [B, C, F] at positions ``start .. start + C - 1``
    (``start`` may be traced), ``valid`` [B, C] (a state has no causal
    shelter from padding: a row that is not valid leaves it as it
    was). Dense prefill runs it once over the padded prompt from
    empty states, which ``caches`` then holds; the gateway's admission
    runs it chunk after chunk against the sequence's state page
    through the pager's subclass, which overrides the three hooks."""

    def __init__(self, dims, start, valid, caches=()):
        self.dims = dims
        self.start = start
        self.valid = valid
        self.caches = list(caches)

    def state(self, li):            # before the chunk
        return self.caches[li]

    def history(self, li):          # the chunks before this one: none
        return None

    def keep(self, li, state, *history):
        self.caches[li] = state

    def attend(self, li, mha, h):
        dims, start = self.dims, self.start
        b, c, f = h.shape
        n_kv = dims.n_kv_heads

        def rotate(z):      # [B*C, heads, d]
            return rotary_embedding(
                z.reshape(b, c, *z.shape[1:]), dims.rope_theta,
                offset=start).reshape(z.shape)

        with devtime.scope("ops.retention_prefill"):
            q, k, v, log_g = retention.project(
                mha, h.reshape(b * c, f), dims.n_heads, n_kv, rotate,
                RMSNORM_EPS)
            a, *carried = retention.retention_chunk(
                q.reshape(b, c, dims.n_heads, -1),
                k.reshape(b, c, n_kv, -1), v.reshape(b, c, n_kv, -1),
                log_g.reshape(b, c, n_kv), self.valid, self.state(li),
                history=self.history(li), start=start)
        self.keep(li, *carried)
        return a.reshape(b, c, -1)


class DenseSSM(_AtPosition):
    """A Mamba-2 layer's "cache" is what the sequence carries: the
    float32 state ``[rows, N, H P]`` and the convolution's tail
    ``[rows, K - 1, channels]``, both rewritten once a position."""

    def attend(self, li, mha, h):
        a, state, tail = ssm.mixer_rows(mha, h, self.dims.hybrid,
                                        *self.caches[li])
        self.caches[li] = (state, tail)
        return a


class SSMRows:
    """Mamba-2 over a chunk of rows a sequence, by the chunked form:
    ``h`` [B, C, F] (or, from a program that runs its rows flat, the
    [C, F] of ONE sequence), ``valid`` [B, C] a prefix of the chunk (a
    row that is not valid leaves state and tail as they were). Dense
    prefill runs it once over the padded prompt from empty states,
    which ``caches`` then holds; the gateway's admission runs it chunk
    after chunk against the sequence's state page through the pager's
    subclass, which overrides the two hooks."""

    def __init__(self, dims, valid, caches=()):
        self.dims = dims
        self.valid = valid
        self.caches = list(caches)

    def state(self, li):            # (state, tail) before the chunk
        return self.caches[li]

    def keep(self, li, state, tail):
        self.caches[li] = (state, tail)

    def attend(self, li, mha, h):
        flat = h.ndim == 2
        a, *carried = ssm.mixer_chunk(
            mha, h[None] if flat else h, self.dims.hybrid, self.valid,
            *self.state(li))
        self.keep(li, *carried)
        return a[0] if flat else a

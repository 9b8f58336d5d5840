"""Attention layers.

Reference: org.deeplearning4j.nn.conf.layers.SelfAttentionLayer /
LearnedSelfAttentionLayer / RecurrentAttentionLayer (deeplearning4j-nn)
over libnd4j ops ``dot_product_attention`` /
``multi_head_dot_product_attention``; plus the transformer-era stack
(MultiHeadAttention, TransformerEncoderBlock, positional embeddings) the
BERT-base BASELINE config needs. Long-context ring attention lives in
``parallel.ring_attention``.

All shapes [B, T, F]; mask [B, T] (key mask). Attention math is
``jax.nn.dot_product_attention`` — XLA fuses it into flash-attention-
style blocks on TPU.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.core import LayerNormalization
from deeplearning4j_tpu.nn import weights as winit


def _split_heads(x, n_heads):
    b, t, f = x.shape
    return x.reshape(b, t, n_heads, f // n_heads)


def _merge_heads(x):
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d)


def rotary_tables(pos, inv_freq, factor: float = 1.0):
    """``cos, sin [len(pos), len(inv_freq)]`` of positions ``pos``
    against the frequencies ``inv_freq``, float32, both times
    ``factor`` (a published ``attention_factor``; 1: no multiply)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos, sin


def rotary_turn(x, cos, sin):
    """``x [..., D]`` with its leading ``R = 2 cos.shape[-1]`` features
    turned, feature ``i`` with ``i + R/2`` (the half-split pairing
    INSIDE the rotated features), the other ``D - R`` as they are;
    ``cos``/``sin`` broadcast against ``x``'s leading axes."""
    half = cos.shape[-1]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:2 * half]
    turned = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if 2 * half < x.shape[-1]:
        turned.append(x[..., 2 * half:])
    return jnp.concatenate(turned, axis=-1)


def rotary_embedding(x, theta: float = 10000.0, offset=0, *,
                     rotary_dim=None, inv_freq=None, factor: float = 1.0):
    """Rotary position embedding (RoPE) on [B, T, H, D] (D even):
    HALF-SPLIT pairing (GPT-NeoX convention — feature i rotates with
    feature i + D/2, NOT the interleaved (i, i+1) GPT-J convention;
    permute Wq/Wk columns when importing interleaved-RoPE weights).
    Scores depend only on RELATIVE position — the modern long-context
    positional scheme. ``offset`` shifts the position index (KV-cache
    decoding). ``theta=None``: no positional term, ``x`` as it is.
    A rule that is not the plain one (``ops.rotary.RopeRule``'s
    fields): ``rotary_dim`` leading features of a head turn (feature
    ``i`` with ``i + rotary_dim / 2``) and the rest pass; ``inv_freq``
    ``[rotary_dim / 2]`` replaces ``theta``'s frequencies; ``factor``
    multiplies cos and sin."""
    if theta is None:
        return x
    if rotary_dim is not None or inv_freq is not None or factor != 1.0:
        half = (rotary_dim or x.shape[-1]) // 2
        if inv_freq is None:
            inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32)
                                 / half)
        cos, sin = rotary_tables(
            offset + jnp.arange(x.shape[1], dtype=jnp.float32), inv_freq,
            factor)
        return rotary_turn(x, cos[None, :, None, :], sin[None, :, None, :])
    b, t, h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = offset + jnp.arange(t, dtype=jnp.float32)
    ang = pos[:, None] * freqs[None, :]            # [T, D/2]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def repeat_kv_heads(k, n_heads: int):
    """Grouped-query attention: broadcast ``n_kv`` key/value heads to
    ``n_heads`` query heads ([B, T, n_kv, D] → [B, T, n_heads, D])."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    if n_heads % n_kv:
        raise ValueError(f"n_heads={n_heads} not divisible by "
                         f"n_kv_heads={n_kv}")
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def _use_flash(q, k, causal: bool = False) -> bool:
    """Platform-helper gate: route to the Pallas flash kernel when the
    KEY sequence is long enough for the blockwise kernel to win (O(Tk)
    memory, skipped dead blocks), including cross-attention — Tq may
    differ. Tiny-Tq shapes (a scan step's single query, learned-query
    pooling) stay on the einsum: their score tile is already O(Tk) and
    the kernel would pad Tq to a full 128-row MXU block per launch.
    Causal with Tq > Tk stays on the einsum too: its leading Tq−Tk
    rows have NO live keys, and the two paths define that degenerate
    row differently (kernel: zeros; einsum: uniform average).
    Threshold via DL4J_TPU_FLASH_MIN_T (crossover measured on v5e,
    tools/flash_crossover.py). ``DL4J_TPU_KERNEL_FORCE`` skips the
    platform/size gates (interpret-mode kernel on CPU) so CI can
    exercise the dispatch decision itself; the SEMANTIC refusals —
    causal Tq > Tk, float64 — hold either way."""
    from deeplearning4j_tpu.environment import get_flag
    semantic_ok = (not (causal and q.shape[1] > k.shape[1])
                   and q.dtype != jnp.float64)
    if get_flag("DL4J_TPU_KERNEL_FORCE"):
        return semantic_ok
    return (semantic_ok
            and k.shape[1] >= get_flag("DL4J_TPU_FLASH_MIN_T")
            and q.shape[1] >= 128
            and jax.default_backend() == "tpu")


def scaled_dot_attention(q, k, v, mask=None, causal=False, window=None,
                         lengths=None):
    """q,k,v: [B, T, H, D] (head axis 2); ``k``/``v`` may carry fewer
    heads (GQA); Tq and Tk may differ (causal is then END-ALIGNED:
    query i attends keys ≤ i + Tk − Tq). mask: [B, Tk] key mask.
    ``window`` (causal only): a query sees the last ``window`` keys
    up to its own, its own included. ``lengths`` (causal, forward
    only): int32 ``[B]``, the rows of each batch row that carry a
    token. What a row at or past it gets is unspecified: the flash
    kernel spends nothing on it and returns zeros, the einsum ignores
    the lengths.

    Explicit einsum+softmax (not jax.nn.dot_product_attention, which is
    not exact in float64 — breaks gradient checking). Platform-helper
    dispatch (the reference's cuDNN-helper pattern, SURVEY §2.3): on
    TPU with long key sequences the Pallas flash kernel is used instead
    — O(Tk) memory, 1.2-1.7x faster than the einsum at T>=4k, and
    GQA-native (one kv block read per head group).
    """
    if _use_flash(q, k, causal):
        # masked sequences take the flash path too (per-example key
        # mask operand in the kernel) — every padded-batch NLP workload
        # stays O(T) memory instead of falling back to the [T,T] einsum
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        if window is not None or lengths is not None:
            # forward only (inference prefill)
            return flash_attention(q, k, v, causal=causal, mask=mask,
                                   window=window, lengths=lengths)
        return flash_attention(q, k, v, causal=causal, mask=mask)
    return plain_attention(q, k, v, mask, causal, window)


def causal_pairs(t: int, n: int, lanes: int, dtype, window=None):
    """What the flash kernel multiplies for ONE head of a causal
    self-attention over ``t`` rows of which the first ``n`` carry a
    token, in (query, key) pairs, ``(need, done)`` as plain integers:
    ``need`` the keys the tokens see, ``done`` the kernel's block
    areas (``ops.pallas_kernels.prefill_pairs``: the kernel's own
    bounds). ``None`` where :func:`scaled_dot_attention` hands such a
    call to the einsum: the kernel multiplies nothing there."""
    from deeplearning4j_tpu.ops.pallas_kernels import prefill_pairs
    dtype = jnp.dtype(dtype)
    rows = jax.ShapeDtypeStruct((1, t, 1, lanes), dtype)
    if not _use_flash(rows, rows, True):
        return None
    return prefill_pairs(t, n, window, lanes, dtype.itemsize)


def plain_attention(q, k, v, mask=None, causal=False, window=None):
    """:func:`scaled_dot_attention`'s einsum form, whatever the
    platform and the lengths: what autodiff runs for a windowed layer
    (the windowed flash kernel has no backward)."""
    d = q.shape[-1]
    k = repeat_kv_heads(k, q.shape[2])
    v = repeat_kv_heads(v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    neg = jnp.asarray(-1e30 if q.dtype == jnp.float64 else -1e9, q.dtype)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :] > 0, logits, neg)
    if causal:
        tq, tk = logits.shape[-2:]
        cm = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        if window is not None:
            cm = cm & ~jnp.tril(jnp.ones((tq, tk), bool),
                                tk - tq - window)
        logits = jnp.where(cm, logits, neg)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


@register_layer
@dataclass
class MultiHeadAttention(Layer):
    """Self multi-head attention projection block (reference
    multi_head_dot_product_attention op + AttentionVertex).

    ``sequence_parallel``: ``"ring"`` | ``"zigzag_ring"`` |
    ``"ulysses"`` | ``None`` — when an ambient
    ``parallel.distributed_context`` is active, the attention runs
    sequence-parallel over its mesh (ring ppermute, load-balanced
    zigzag ring, or all-to-all head swap); outside a context it falls
    back to local attention, so the same model config runs single- and
    multi-chip. Entering/exiting the context invalidates the owning
    net's jitted traces, so the decision is never stale.
    """
    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 1
    causal: bool = False
    project_out: bool = True
    sequence_parallel: Optional[str] = None
    n_kv_heads: Optional[int] = None   # grouped-query attention
    rope: bool = False                 # rotary position embeddings
    rope_theta: Optional[float] = 10000.0
    #: the scores' scale in ``d^-1/2``'s place (folded into the query)
    score_scale: Optional[float] = None
    #: a sliding window over the keys (causal only): a query sees the
    #: last ``window`` keys, its own included; by the plain masked form
    window: Optional[int] = None
    #: a head's width where it is not ``n_out / n_heads`` (a published
    #: decoder whose heads' joined width differs from its hidden
    #: width: ``Wq [n_in, H d]``, ``Wo [H d, n_out]``)
    head_dim: Optional[int] = None
    #: an ``ops.rotary.RopeRule`` (or the dict a serialized layer
    #: carries) where the layer's rotation is not ``rope_theta``'s
    #: plain one over the whole head: its base, rotated width,
    #: frequencies and factor replace ``rope_theta``
    rope_rule: Optional[Any] = None
    #: a sigmoid gate a head on the attention's output, in front of
    #: ``Wo``, read from the layer's input rows: ``Wog [n_in, H]``
    gate: bool = False

    _SP_MODES = (None, "ring", "ulysses", "zigzag_ring")

    def _attend(self, q, k, v, mask):
        """``k``/``v`` may carry fewer heads than ``q`` (GQA): the
        ring paths keep the SMALL kv on the wire and the flash kernels
        read one kv block per head group; only Ulysses (head-axis
        all-to-all) needs the broadcast."""
        if self.sequence_parallel not in self._SP_MODES:
            # reject typos even single-chip, where no context is active
            raise ValueError(
                f"unknown sequence_parallel mode "
                f"{self.sequence_parallel!r} (ring|ulysses|zigzag_ring)")
        n_heads = q.shape[2]
        if self.window is not None:
            if self.sequence_parallel or not self.causal:
                raise ValueError("a sliding window is causal and has "
                                 "no sequence-parallel form")
            return plain_attention(q, k, v, mask, True, self.window)
        if self.sequence_parallel:
            from deeplearning4j_tpu.parallel.mesh import active_context
            ctx = active_context()
            if ctx is not None:
                if self.sequence_parallel == "ring":
                    from deeplearning4j_tpu.parallel.ring_attention \
                        import ring_self_attention
                    return ring_self_attention(
                        q, k, v, ctx.mesh, axis_name=ctx.axis_name,
                        mask=mask, causal=self.causal,
                        batch_axis=getattr(ctx, "batch_axis", None),
                        head_axis=getattr(ctx, "head_axis", None))
                if self.sequence_parallel == "ulysses":
                    from deeplearning4j_tpu.parallel.ulysses import \
                        ulysses_self_attention
                    return ulysses_self_attention(
                        q, repeat_kv_heads(k, n_heads),
                        repeat_kv_heads(v, n_heads), ctx.mesh,
                        axis_name=ctx.axis_name,
                        mask=mask, causal=self.causal)
                if self.sequence_parallel == "zigzag_ring":
                    # load-balanced causal ring; tokens permuted into
                    # zigzag layout around the call (pre-permute the
                    # DATA once instead for production pipelines)
                    from deeplearning4j_tpu.parallel.ring_attention \
                        import (zigzag_permute,
                                zigzag_ring_self_attention,
                                zigzag_unpermute)
                    if not self.causal:
                        raise ValueError("zigzag_ring is causal-only")
                    n = ctx.mesh.shape[ctx.axis_name]
                    zmask = (None if mask is None
                             else zigzag_permute(mask, n, axis=1))
                    o = zigzag_ring_self_attention(
                        zigzag_permute(q, n), zigzag_permute(k, n),
                        zigzag_permute(v, n), ctx.mesh,
                        axis_name=ctx.axis_name, mask=zmask,
                        batch_axis=getattr(ctx, "batch_axis", None),
                        head_axis=getattr(ctx, "head_axis", None))
                    return zigzag_unpermute(o, n)
        return scaled_dot_attention(q, k, v, mask, self.causal)

    def init(self, key, input_shape, dtype=jnp.float32):
        n_in = self.n_in or input_shape[-1]
        n_out = self.n_out or n_in
        if self.head_dim is None and n_out % self.n_heads:
            raise ValueError(f"n_out={n_out} not divisible by "
                             f"n_heads={self.n_heads}")
        n_kv = self.n_kv_heads or self.n_heads
        if self.n_heads % n_kv:
            raise ValueError(f"n_heads={self.n_heads} not divisible "
                             f"by n_kv_heads={n_kv}")
        hd = self.head_dim or n_out // self.n_heads
        kv_out = hd * n_kv
        wi = winit.get(self.weight_init or "xavier")
        kq, kk, kv_, ko = jax.random.split(key, 4)
        params = {"Wq": wi(kq, (n_in, hd * self.n_heads), dtype),
                  "Wk": wi(kk, (n_in, kv_out), dtype),
                  "Wv": wi(kv_, (n_in, kv_out), dtype)}
        if self.project_out:
            params["Wo"] = wi(ko, (hd * self.n_heads, n_out), dtype)
            params["bo"] = jnp.zeros((n_out,), dtype)
        if self.gate:
            params["Wog"] = wi(jax.random.fold_in(key, 4),
                               (n_in, self.n_heads), dtype)
        t = input_shape[0]
        return params, {}, (t, n_out)

    def _rotate(self, z):
        if self.rope_rule is None:
            return rotary_embedding(z, self.rope_theta)
        from deeplearning4j_tpu.ops.rotary import RopeRule
        rule = RopeRule.of(self.rope_rule)
        return rotary_embedding(
            z, rule.theta, rotary_dim=rule.rotary_dim,
            inv_freq=rule.inv_freq(z.shape[-1]), factor=rule.factor)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        n_kv = self.n_kv_heads or self.n_heads
        q = _split_heads(x @ params["Wq"], self.n_heads)
        k = _split_heads(x @ params["Wk"], n_kv)
        v = _split_heads(x @ params["Wv"], n_kv)
        if self.rope:
            q, k = self._rotate(q), self._rotate(k)
        if self.score_scale is not None:
            q = q * jnp.asarray(
                self.score_scale * math.sqrt(q.shape[-1]), q.dtype)
        o = self._attend(q, k, v, mask)
        if self.gate:
            o = o * jax.nn.sigmoid(x @ params["Wog"])[..., None]
        o = _merge_heads(o)
        if self.project_out:
            o = o @ params["Wo"] + params["bo"]
        if mask is not None:
            o = o * mask[..., None].astype(o.dtype)
        return self._maybe_dropout(self._act()(o), train, rng), state


@register_layer
@dataclass
class SelfAttentionLayer(MultiHeadAttention):
    """Reference SelfAttentionLayer: self-attention, output per timestep."""


@register_layer
@dataclass
class LearnedSelfAttentionLayer(Layer):
    """Attention with ``n_queries`` learned query vectors (reference
    LearnedSelfAttentionLayer) — pools [B,T,F] to [B,Q,F_out]."""
    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 1
    n_queries: int = 1

    def init(self, key, input_shape, dtype=jnp.float32):
        n_in = self.n_in or input_shape[-1]
        n_out = self.n_out or n_in
        wi = winit.get(self.weight_init or "xavier")
        kq, kk, kv, kp = jax.random.split(key, 4)
        params = {"Q": wi(kq, (self.n_queries, n_out), dtype),
                  "Wk": wi(kk, (n_in, n_out), dtype),
                  "Wv": wi(kv, (n_in, n_out), dtype)}
        return params, {}, (self.n_queries, n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b = x.shape[0]
        q = jnp.broadcast_to(params["Q"][None], (b,) + params["Q"].shape)
        q = _split_heads(q, self.n_heads)
        k = _split_heads(x @ params["Wk"], self.n_heads)
        v = _split_heads(x @ params["Wv"], self.n_heads)
        o = _merge_heads(scaled_dot_attention(q, k, v, mask))
        return self._act()(o), state

    def propagate_mask(self, mask, input_shape):
        return None  # fixed n_queries output, fully valid


@register_layer
@dataclass
class PositionalEmbeddingLayer(Layer):
    """Learned positional embeddings added to [B,T,F] (BERT-style)."""
    max_len: int = 512

    def init(self, key, input_shape, dtype=jnp.float32):
        t, f = input_shape
        params = {"pos": jax.random.normal(
            key, (self.max_len, f), dtype) * 0.02}
        return params, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        t = x.shape[1]
        return x + params["pos"][None, :t, :], state


@register_layer
@dataclass
class TransformerEncoderBlock(Layer):
    """Pre-LN transformer encoder block: MHA + MLP with residuals.

    The reference has no transformer block layer (its BERT support comes
    through TF import, SURVEY §3.4) — provided natively here since the
    BASELINE BERT config demands it.
    """
    n_in: Optional[int] = None
    n_heads: int = 8
    ffn_mult: float = 4
    causal: bool = False
    sequence_parallel: Optional[str] = None

    def init(self, key, input_shape, dtype=jnp.float32):
        f = self.n_in = self.n_in or input_shape[-1]
        wi = winit.get(self.weight_init or "xavier")
        ks = jax.random.split(key, 6)
        self._mha = MultiHeadAttention(
            n_in=f, n_out=f, n_heads=self.n_heads, causal=self.causal,
            sequence_parallel=self.sequence_parallel)
        self._ln1 = LayerNormalization()
        self._ln2 = LayerNormalization()
        pa, _, _ = self._mha.init(ks[0], input_shape, dtype)
        p1, _, _ = self._ln1.init(ks[1], input_shape, dtype)
        p2, _, _ = self._ln2.init(ks[2], input_shape, dtype)
        hid = int(round(f * self.ffn_mult))
        params = {"mha": pa, "ln1": p1, "ln2": p2,
                  "W1": wi(ks[3], (f, hid), dtype),
                  "b1": jnp.zeros((hid,), dtype),
                  "W2": wi(ks[4], (hid, f), dtype),
                  "b2": jnp.zeros((f,), dtype)}
        return params, {}, tuple(input_shape)

    def _subs(self, input_shape=None):
        f = self.n_in
        if not hasattr(self, "_mha"):
            self._mha = MultiHeadAttention(
                n_in=f, n_out=f, n_heads=self.n_heads,
                causal=self.causal,
                sequence_parallel=self.sequence_parallel)
            self._ln1 = LayerNormalization()
            self._ln2 = LayerNormalization()

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        self._subs()
        r1, r2 = (jax.random.split(rng) if rng is not None else (None, None))
        h, _ = self._ln1.apply(params["ln1"], {}, x)
        a, _ = self._mha.apply(params["mha"], {}, h, train=train, rng=r1,
                               mask=mask)
        x = x + a
        h, _ = self._ln2.apply(params["ln2"], {}, x)
        h = jax.nn.gelu(h @ params["W1"] + params["b1"])
        h = h @ params["W2"] + params["b2"]
        x = x + self._maybe_dropout(h, train, r2)
        return x, state


@register_layer
@dataclass
class PowerRetention(Layer):
    """The power-retention sequence mixer (``ops/retention.py`` has
    the equations): grouped queries against a gated second-power
    state in place of a softmax over every cached key. Projections as
    :class:`MultiHeadAttention` has them, plus a per-head RMSNorm of q
    and k (gains ``q_gamma``/``k_gamma``), rotary positions, and a
    gate ``g = sigmoid(h Wgate + bgate)`` per KV head. The training
    forward is the chunked form in plain ``jnp``
    (``retention.TRAIN_CHUNK`` positions by the attention form, the
    state across them), differentiated by autodiff; the gate's bias
    starts at ``retention.GATE_BIAS_INIT``. Causal by construction."""
    n_in: Optional[int] = None
    n_heads: int = 1
    n_kv_heads: Optional[int] = None
    rope_theta: float = 10000.0

    def init(self, key, input_shape, dtype=jnp.float32):
        from deeplearning4j_tpu.ops import retention
        f = self.n_in or input_shape[-1]
        if f % self.n_heads:
            raise ValueError(f"n_in={f} not divisible by "
                             f"n_heads={self.n_heads}")
        n_kv = self.n_kv_heads or self.n_heads
        if self.n_heads % n_kv:
            raise ValueError(f"n_heads={self.n_heads} not divisible "
                             f"by n_kv_heads={n_kv}")
        hd = f // self.n_heads
        if hd % 8:
            raise ValueError(f"head_dim={hd} must be a multiple of 8 "
                             "(the retention state's row tiles)")
        wi = winit.get(self.weight_init or "xavier")
        kq, kk, kv_, ko, kg = jax.random.split(key, 5)
        params = {"Wq": wi(kq, (f, f), dtype),
                  "Wk": wi(kk, (f, hd * n_kv), dtype),
                  "Wv": wi(kv_, (f, hd * n_kv), dtype),
                  "Wo": wi(ko, (f, f), dtype),
                  "bo": jnp.zeros((f,), dtype),
                  "Wgate": wi(kg, (f, n_kv), dtype),
                  "bgate": jnp.full((n_kv,), retention.GATE_BIAS_INIT,
                                    dtype),
                  "q_gamma": jnp.ones((hd,), dtype),
                  "k_gamma": jnp.ones((hd,), dtype)}
        return params, {}, (input_shape[0], f)

    def apply(self, params, state, x, *, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.nn.layers.core import RMSNORM_EPS
        from deeplearning4j_tpu.ops import retention
        b, t, f = x.shape
        n_kv = self.n_kv_heads or self.n_heads

        def rotate(z):      # [B*T, heads, d] -> rotated by position
            return rotary_embedding(
                z.reshape(b, t, *z.shape[1:]),
                self.rope_theta).reshape(z.shape)

        q, k, v, log_g = retention.project(
            params, x.reshape(b * t, f), self.n_heads, n_kv, rotate,
            RMSNORM_EPS)
        y, _ = retention.retention_sequence(
            q.reshape(b, t, self.n_heads, -1),
            k.reshape(b, t, n_kv, -1), v.reshape(b, t, n_kv, -1),
            log_g.reshape(b, t, n_kv), retention.TRAIN_CHUNK,
            valid=None if mask is None else mask.astype(bool))
        o = _merge_heads(y) @ params["Wo"] + params["bo"]
        if mask is not None:
            o = o * mask[..., None].astype(o.dtype)
        return o, state


def latent_attention_expanded(mha, h, spec, n_heads: int, theta: float,
                              lengths=None):
    """Causal latent attention of whole sequences ``h [B, T, F]`` in
    the EXPANDED form (``ops/latent.py``): every position's K and V
    made from its latent, through :func:`scaled_dot_attention`
    (flash-dispatched on the TPU). That takes ONE width for queries,
    keys and values, so all three are padded with zeros to whole
    128-lane tiles (192-wide keys and 128-wide values to 256: zeros
    add nothing to a score, and the values' tail is cut off again),
    and the softmax scale is folded into the query. ``lengths`` as
    :func:`scaled_dot_attention`'s (a padded prompt's). Returns the
    mixer's output ``[B, T, H * v]`` and the positions' latent rows
    ``[B, T, kv_rank + rope]`` (what a cache keeps)."""
    from deeplearning4j_tpu.ops import latent
    b, t, f = h.shape
    width = latent.lanes(spec.nope + spec.rope)

    def padded(*parts):
        have = sum(p.shape[-1] for p in parts)
        return jnp.concatenate(parts + (jnp.zeros(
            (b, t, n_heads, width - have), parts[0].dtype),), axis=-1)

    q_nope, q_rope, row = latent.project(
        mha, h.reshape(b * t, f), spec, n_heads, theta,
        jnp.tile(jnp.arange(t), b))
    row = row.reshape(b, t, -1)
    k_nope, v = latent.expand(mha, row, spec, n_heads)
    q = padded(q_nope.reshape(b, t, n_heads, -1),
               q_rope.reshape(b, t, n_heads, -1))
    k = padded(k_nope, jnp.broadcast_to(
        row[:, :, None, spec.kv_rank:], (b, t, n_heads, spec.rope)))
    # scaled_dot_attention divides by the root of the width
    fold = latent.softmax_scale(spec) * width ** 0.5
    a = scaled_dot_attention((q * fold).astype(q.dtype), k, padded(v),
                             causal=True, lengths=lengths)
    return a[..., :spec.v].reshape(b, t, -1), row


@register_layer
@dataclass
class LatentAttention(Layer):
    """Multi-head latent attention (``ops/latent.py`` has the
    equations): queries through a normed low-rank ``c_q``, keys and
    values expanded from ONE normed latent a position beside one
    rotary key shared by all heads. Its parameters take
    :class:`MultiHeadAttention`'s place, ``params["mha"]``: ``Wqa``,
    ``qa_gamma``, ``Wqb``, ``Wkva``, ``kv_gamma``, ``Wkvb``, ``Wo``
    (no biases, as published). The training forward is the expanded
    form (:func:`latent_attention_expanded`), differentiated through
    ``scaled_dot_attention``. Causal by
    construction; ``spec`` is an ``ops.latent.LatentSpec`` (or the
    dict a serialized layer carries)."""
    n_in: Optional[int] = None
    n_heads: int = 1
    rope_theta: float = 10000.0
    spec: Optional[Any] = None

    def init(self, key, input_shape, dtype=jnp.float32):
        from deeplearning4j_tpu.ops.latent import LatentSpec
        f = self.n_in or input_shape[-1]
        spec = LatentSpec.of(self.spec)
        h = self.n_heads
        wi = winit.get(self.weight_init or "xavier")
        ks = jax.random.split(key, 5)
        params = {
            "Wqa": wi(ks[0], (f, spec.q_rank), dtype),
            "qa_gamma": jnp.ones((spec.q_rank,), dtype),
            "Wqb": wi(ks[1], (spec.q_rank, h * (spec.nope + spec.rope)),
                      dtype),
            "Wkva": wi(ks[2], (f, spec.row), dtype),
            "kv_gamma": jnp.ones((spec.kv_rank,), dtype),
            "Wkvb": wi(ks[3], (spec.kv_rank, h * (spec.nope + spec.v)),
                       dtype),
            "Wo": wi(ks[4], (h * spec.v, f), dtype)}
        return params, {}, (input_shape[0], f)

    def apply(self, params, state, x, *, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.ops.latent import LatentSpec
        a, _ = latent_attention_expanded(
            params, x, LatentSpec.of(self.spec), self.n_heads,
            self.rope_theta)
        o = a @ params["Wo"]
        if mask is not None:
            o = o * mask[..., None].astype(o.dtype)
        return o, state


@register_layer
@dataclass
class Mamba2Mixer(Layer):
    """The Mamba-2 sequence mixer (``ops/ssm.py`` has the equations):
    one in-projection to gate, convolved ``x | B | C`` and step sizes,
    a short causal depthwise convolution, the selective state-space
    recurrence with a float32 state a head, a gated RMSNorm. Its
    parameters take :class:`MultiHeadAttention`'s place,
    ``params["mha"]``: ``Win``, ``conv_w`` ``[taps, channels]``,
    ``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``norm_gamma`` and
    ``Wo`` (no projection bias, as published). The training forward is
    the chunked form in plain ``jnp``, differentiated by autodiff.
    Init is the published one: ``A`` uniform in [1, 16], the step's
    bias the inverse softplus of a log-uniform step in [1e-3, 1e-1],
    ``D`` = 1, the convolution uniform by its fan-in. Causal by
    construction; ``spec`` is an ``ops.ssm.HybridSpec`` (or the dict a
    serialized layer carries)."""
    n_in: Optional[int] = None
    spec: Optional[Any] = None

    def init(self, key, input_shape, dtype=jnp.float32):
        from deeplearning4j_tpu.ops.ssm import HybridSpec
        f = self.n_in or input_shape[-1]
        spec = HybridSpec.of(self.spec)
        wi = winit.get(self.weight_init or "xavier")
        ks = jax.random.split(key, 6)
        n_h, taps = spec.n_heads, spec.d_conv
        step = jnp.exp(jax.random.uniform(
            ks[2], (n_h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        bound = 1.0 / math.sqrt(taps)
        params = {
            "Win": wi(ks[0], (f, spec.in_width), dtype),
            "conv_w": jax.random.uniform(
                ks[1], (taps, spec.conv_dim), dtype, -bound, bound),
            "conv_b": jax.random.uniform(
                ks[5], (spec.conv_dim,), dtype, -bound, bound),
            # softplus(dt_bias) = step
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "A_log": jnp.log(jax.random.uniform(
                ks[3], (n_h,), jnp.float32, 1.0, 16.0)).astype(dtype),
            "D": jnp.ones((n_h,), dtype),
            "norm_gamma": jnp.ones((spec.d_inner,), dtype),
            "Wo": wi(ks[4], (spec.d_inner, f), dtype)}
        return params, {}, (input_shape[0], f)

    def apply(self, params, state, x, *, train=False, rng=None,
              mask=None):
        from deeplearning4j_tpu.ops import ssm
        spec = ssm.HybridSpec.of(self.spec)
        b, t, _ = x.shape
        zero = ssm.zero_state(b, spec, x.dtype)
        if mask is None:
            y, _, _ = ssm.mixer_chunk(params, x, spec,
                                      jnp.ones((b, t), bool), *zero)
            return y @ params["Wo"], state
        # A sequence is its valid rows, wherever the mask puts them:
        # ``mixer_chunk`` wants them first (its convolution takes the
        # rows before a position as they lie), so they are moved
        # there, in order, and the outputs moved back.
        valid = mask.astype(bool)
        order = jnp.argsort(~valid, axis=1, stable=True)
        y, _, _ = ssm.mixer_chunk(
            params, jnp.take_along_axis(x, order[..., None], axis=1),
            spec, jnp.take_along_axis(valid, order, axis=1), *zero)
        y = jnp.take_along_axis(y, jnp.argsort(order, axis=1)[..., None],
                                axis=1)
        return (y @ params["Wo"]) * mask[..., None].astype(y.dtype), state


@register_layer
@dataclass
class TransformerDecoderBlock(Layer):
    """Pre-RMSNorm causal decoder block (modern-LM style): grouped-
    query attention with rotary embeddings + SwiGLU MLP, residuals
    around both. The reference has no decoder-only transformer (its
    LM story is char-RNN + imported BERT); this is the native causal-LM
    building block, sequence-parallel-ready via ``sequence_parallel``.

    ``remat=True`` wraps the block in ``jax.checkpoint``: activations
    inside the block are recomputed during backward instead of stored —
    the standard FLOPs-for-HBM trade that makes deep long-context
    stacks fit (peak activation memory drops from O(layers·T·F) to
    O(T·F) + per-block recompute).
    """
    n_in: Optional[int] = None
    n_heads: int = 8
    n_kv_heads: Optional[int] = None
    # float allowed: 8/3 is the LLaMA convention that makes a SwiGLU
    # block parameter-match a classic 4x two-matrix MLP
    ffn_mult: float = 4
    #: None: no positional term (the mixer is all a position has)
    rope_theta: Optional[float] = 10000.0
    sequence_parallel: Optional[str] = None
    remat: bool = False
    #: the sequence mixer: "softmax" (attention over every cached key),
    #: "power_retention" (:class:`PowerRetention`), "latent"
    #: (:class:`LatentAttention`, sized by ``latent``) or "mamba2"
    #: (:class:`Mamba2Mixer`, sized by ``hybrid``); its parameters
    #: take the same place, ``params["mha"]``
    mixer: str = "softmax"
    #: an ``ops.latent.LatentSpec`` (``mixer="latent"``)
    latent: Optional[Any] = None
    #: an ``ops.ssm.HybridSpec``: the Mamba sizes (``mixer="mamba2"``)
    hybrid: Optional[Any] = None
    #: what a published decoder multiplies each half's addition to the
    #: residual stream by (None: nothing)
    residual_multiplier: Optional[float] = None
    #: the softmax mixer's score scale in ``d^-1/2``'s place
    score_scale: Optional[float] = None
    #: eps of the block's two norms (None: :data:`core.RMSNORM_EPS`)
    norm_eps: Optional[float] = None
    #: the feed-forward: "dense" (SwiGLU of ``ffn_mult`` widths) or
    #: "experts" (``ops/moe.py``'s layer, sized by ``experts``, an
    #: ``ops.moe.ExpertSpec``: its parameters under ``params["moe"]``)
    ffn: str = "dense"
    experts: Optional[Any] = None
    #: the softmax mixer's sliding window (None: every earlier key)
    window: Optional[int] = None
    #: a softmax head's width where it is not ``n_in / n_heads``
    head_dim: Optional[int] = None
    #: the softmax mixer's rotary rule where it is not ``rope_theta``'s
    #: plain one (an ``ops.rotary.RopeRule`` or its dict), and whether
    #: a sigmoid gate a head multiplies its output in front of ``Wo``
    rope_rule: Optional[Any] = None
    attn_gate: bool = False

    def _subs(self):
        if not hasattr(self, "_mha"):
            from deeplearning4j_tpu.nn.layers.core import RMSNorm
            f = self.n_in
            if self.mixer == "power_retention":
                if self.sequence_parallel:
                    raise ValueError(
                        "mixer='power_retention' has no sequence-"
                        "parallel form: the state passes from chunk "
                        "to chunk, there is no ring to turn")
                self._mha = PowerRetention(
                    n_in=f, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads,
                    rope_theta=self.rope_theta)
            elif self.mixer == "latent":
                if self.sequence_parallel:
                    raise ValueError(
                        "mixer='latent' has no sequence-parallel form "
                        "here: the ring carries KV heads, not latents")
                self._mha = LatentAttention(
                    n_in=f, n_heads=self.n_heads,
                    rope_theta=self.rope_theta, spec=self.latent)
            elif self.mixer == "mamba2":
                if self.sequence_parallel:
                    raise ValueError(
                        "mixer='mamba2' has no sequence-parallel form: "
                        "the state passes from chunk to chunk")
                self._mha = Mamba2Mixer(n_in=f, spec=self.hybrid)
            elif self.mixer != "softmax":
                raise ValueError(
                    f"mixer={self.mixer!r} ('softmax' | "
                    "'power_retention' | 'latent' | 'mamba2')")
            else:
                self._mha = MultiHeadAttention(
                    n_in=f, n_out=f, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, causal=True, rope=True,
                    rope_theta=self.rope_theta,
                    score_scale=self.score_scale,
                    sequence_parallel=self.sequence_parallel,
                    **({} if self.window is None
                       else {"window": self.window}),
                    **({} if self.head_dim is None
                       else {"head_dim": self.head_dim}),
                    **({} if self.rope_rule is None
                       else {"rope_rule": self.rope_rule}),
                    **({"gate": True} if self.attn_gate else {}))
            eps = {} if self.norm_eps is None else {"eps": self.norm_eps}
            self._ln1 = RMSNorm(**eps)
            self._ln2 = RMSNorm(**eps)

    def init(self, key, input_shape, dtype=jnp.float32):
        f = self.n_in = self.n_in or input_shape[-1]
        self._subs()
        wi = winit.get(self.weight_init or "xavier")
        ks = jax.random.split(key, 6)
        pa, _, _ = self._mha.init(ks[0], input_shape, dtype)
        p1, _, _ = self._ln1.init(ks[1], input_shape, dtype)
        p2, _, _ = self._ln2.init(ks[2], input_shape, dtype)
        params = {"mha": pa, "ln1": p1, "ln2": p2}
        if self.ffn == "experts":
            from deeplearning4j_tpu.ops.moe import ExpertSpec
            e = ExpertSpec.of(self.experts)
            ke = jax.random.split(ks[3], 7)
            moe = {  # the router stays float32 whatever the dtype
                "Wr": wi(ke[0], (f, e.n_routed), jnp.float32),
                "br": jnp.zeros((e.n_routed,), jnp.float32),
                "Weg": wi(ke[1], (e.n_held, f, e.width), dtype),
                "Weu": wi(ke[2], (e.n_held, f, e.width), dtype),
                "Wed": wi(ke[3], (e.n_held, e.width, f), dtype)}
            if e.n_shared:
                shared = e.n_shared * e.width
                moe.update(Wsg=wi(ke[4], (f, shared), dtype),
                           Wsu=wi(ke[5], (f, shared), dtype),
                           Wsd=wi(ke[6], (shared, f), dtype))
            params["moe"] = moe
        elif self.ffn != "dense":
            raise ValueError(f"ffn={self.ffn!r} ('dense' | 'experts')")
        else:
            hid = int(round(f * self.ffn_mult))
            # SwiGLU: (silu(x W_gate) ⊙ x W_up) W_down
            params.update(Wg=wi(ks[3], (f, hid), dtype),
                          Wu=wi(ks[4], (f, hid), dtype),
                          Wd=wi(ks[5], (hid, f), dtype))
        return params, {}, tuple(input_shape)

    def _body(self, params, x, mask, train, rng):
        from deeplearning4j_tpu.ops import fused_norms
        r1, r2 = (jax.random.split(rng) if rng is not None
                  else (None, None))
        h, _ = self._ln1.apply(params["ln1"], {}, x)
        h1 = h
        a, _ = self._mha.apply(params["mha"], {}, h, train=train,
                               rng=r1, mask=mask)
        res = self.residual_multiplier
        if res is not None:
            a = a * jnp.asarray(res, a.dtype)
        # residual add + RMSNorm as ONE fused epilogue on TPU
        # (ops/fused_norms.py); gate-off runs the exact pre-existing
        # add-then-norm pair
        h, x = fused_norms.add_rms_norm(x, a, params["ln2"]["gamma"],
                                        eps=self._ln2.eps)
        if "moe" in params:
            # the plain form: every held expert on every row, masked
            # by the routing (autodiff runs no data-dependent loop)
            from deeplearning4j_tpu.ops import moe
            spec = moe.ExpertSpec.of(self.experts)
            h, _ = moe.layer(
                params["moe"], h, spec, plain=True,
                **({"route_rows": h1} if spec.route_before_mixer
                   else {}))
        else:
            h = jax.nn.silu(h @ params["Wg"]) * (h @ params["Wu"])
            h = h @ params["Wd"]
        if res is not None:
            h = h * jnp.asarray(res, h.dtype)
        return x + self._maybe_dropout(h, train, r2)

    def apply(self, params, state, x, *, train=False, rng=None,
              mask=None):
        self._subs()
        if self.remat:
            fn = jax.checkpoint(
                lambda p, x: self._body(p, x, mask, train, rng))
            return fn(params, x), state
        return self._body(params, x, mask, train, rng), state


@register_layer
@dataclass
class ClsTokenPoolLayer(Layer):
    """[B,T,F] -> [B,F]: select the first (CLS) token, optionally through
    a tanh pooler dense (BERT's pooler). The reference has no such layer
    — its BERT path pools inside the imported TF graph (SURVEY §3.4)."""
    n_out: int = 0                 # 0: no pooler dense, raw CLS vector
    pooler: bool = False

    def init(self, key, input_shape, dtype=jnp.float32):
        t, f = input_shape
        if self.n_out and not self.pooler:
            raise ValueError("ClsTokenPoolLayer: n_out requires "
                             "pooler=True (no projection otherwise)")
        if self.pooler:
            n = self.n_out or f
            wi = winit.get(self.weight_init or "xavier")
            params = {"W": wi(key, (f, n), dtype),
                      "b": jnp.zeros((n,), dtype)}
            return params, {}, (n,)
        return {}, {}, (f,)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        cls = x[:, 0, :]
        if self.pooler:
            cls = jnp.tanh(cls @ params["W"] + params["b"])
        return cls, state

    def propagate_mask(self, mask, out_len=None):
        return None                # sequence axis is gone


@register_layer
@dataclass
class RecurrentAttentionLayer(Layer):
    """Reference RecurrentAttentionLayer: a SimpleRnn whose step also
    attends over the WHOLE input sequence with the previous hidden
    state as query —
    ``h_t = act(W·x_t + U·h_{t-1} + Wo·attn(h_{t-1}, X, X) + b)``.
    K/V projections are one big MXU matmul outside the ``lax.scan``;
    only the query/attend/update runs per step."""
    n_in: Optional[int] = None
    n_out: int = 0
    n_heads: int = 1

    def init(self, key, input_shape, dtype=jnp.float32):
        n_in = self.n_in or input_shape[-1]
        h = self.n_out
        if h % self.n_heads:
            raise ValueError(f"n_out={h} % n_heads={self.n_heads} != 0")
        wi = winit.get(self.weight_init or "xavier")
        ks = jax.random.split(key, 6)
        params = {"W": wi(ks[0], (n_in, h), dtype),
                  "U": wi(ks[1], (h, h), dtype),
                  "Wq": wi(ks[2], (h, h), dtype),
                  "Wk": wi(ks[3], (n_in, h), dtype),
                  "Wv": wi(ks[4], (n_in, h), dtype),
                  "Wo": wi(ks[5], (h, h), dtype),
                  "b": jnp.zeros((h,), dtype)}
        t = input_shape[0]
        return params, {}, (t, h)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        b, t, _ = x.shape
        h = self.n_out
        nh = self.n_heads
        hd = h // nh
        dt = x.dtype
        act = self._act("tanh")
        xg = jnp.swapaxes(x @ params["W"] + params["b"], 0, 1)  # [T,B,H]
        k = (x @ params["Wk"]).reshape(b, t, nh, hd)
        v = (x @ params["Wv"]).reshape(b, t, nh, hd)
        m = (jnp.ones((t, b, 1), dt) if mask is None
             else jnp.swapaxes(mask, 0, 1)[..., None].astype(dt))
        U, Wq, Wo = params["U"], params["Wq"], params["Wo"]

        def step(hp, inp):
            g, mt = inp
            q = (hp @ Wq).reshape(b, 1, nh, hd)
            a = scaled_dot_attention(q, k, v, mask).reshape(b, h)
            hh = act(g + hp @ U + a @ Wo)
            # masked steps hold state, emit zeros (module convention)
            hn = mt * hh + (1 - mt) * hp
            return hn, hh * mt

        h0 = jnp.zeros((b, h), dt)
        _, ys = jax.lax.scan(step, h0, (xg, m))
        y = jnp.swapaxes(ys, 0, 1)
        return self._maybe_dropout(y, train, rng), state

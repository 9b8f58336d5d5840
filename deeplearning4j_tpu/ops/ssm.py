"""Mamba-2 (the state-space-duality mixer) in plain ``jax.numpy``: the
sequence mixer of a hybrid decoder's ``mamba2`` layers, and the layer
kinds and Mamba sizes such a decoder carries (:class:`HybridSpec`).

Per position, ``h`` the normed input of width F, ``H`` heads of ``P``
features, a state of ``N`` values a feature, ONE group (``B`` and ``C``
are shared by all heads):

- ``[z | xBC | dt] = h Win``, widths ``H P | H P + 2 N | H``, in that
  order (:func:`project`);
- ``xBC <- silu(conv(xBC))``: a depthwise causal convolution over
  time, ``K`` taps and a bias a channel, ``y_t = b + sum_j w_j
  xBC_{t-K+1+j}``, zeros before the sequence (:func:`conv_rows`,
  :func:`conv_chunk`); ``[x | B | C] = xBC``, widths ``H P | N | N``;
- ``Delta_t = softplus(dt_t + dt_bias)`` a head (no clamp), ``a_t =
  exp(Delta_t A)``, ``A = -exp(A_log)`` a head;
- ``H_t = a_t H_{t-1} + Delta_t x_t (x) B_t``, ``y_t = H_t C_t + D
  x_t`` (``D`` a head);
- ``y <- RMSNorm(y * silu(z))`` over all ``H P`` features with a gain
  of its own (the gate BEFORE the norm, :func:`gated_norm`), then the
  block's ``Wo``.

What a sequence carries from position to position is the state and the
last ``K - 1`` rows of the un-convolved ``xBC`` (the convolution's
**tail**). Two forms of the same recurrence live here:
:func:`ssd_chunk` (inside a chunk the masked ``(C B^T . L) X`` product,
before it the carried state: every prefill, the training forward) and
:func:`ssd_step` (one position: ``generate()``, the paged decode
step's fallback). The decode step's kernel over the paged pool is
``ops.pallas_kernels.ssm_decode``.

**The state as it is stored** (every form here and the kernel agree on
it): the float32 matrix ``[N, H P]``, row ``n`` the state value ``n``
of every (head, feature) column. With one group a chunk's state update
is ONE matrix product ``B^T (w . X)`` and its read another, ``C H``;
the kernel streams the matrix in ``[N, 128]`` column blocks whose lanes
are features, so ``x``, the decay and the output are lane rows and only
``B`` and ``C`` (one a slot, not one a head) turn into sublane
columns. States are float32 whatever the compute dtype; the tail is
held in the compute dtype (its rows are copies, not sums).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.obs import devtime

_HIGHEST = jax.lax.Precision.HIGHEST

#: the layer kinds a hybrid decoder may list
KINDS = ("mamba2", "softmax")


@dataclass(frozen=True)
class HybridSpec:
    """A hybrid decoder's layer kinds and the sizes of its ``mamba2``
    mixers, as a published configuration names them. (What a decoder
    multiplies its embedding, residual additions, scores and logits by
    is the model's own, whatever its mixers:
    ``CausalTransformerLM(residual_multiplier=...)``.)"""
    #: one of :data:`KINDS` a layer
    kinds: Tuple[str, ...]
    d_inner: int                    # H * P
    n_heads: int
    d_state: int
    d_conv: int = 4
    #: positions of the chunked form's chunk
    chunk: int = 256
    #: eps of the mixer's gated RMSNorm
    norm_eps: float = 1e-5

    def __post_init__(self):
        object.__setattr__(self, "kinds", tuple(self.kinds))
        bad = sorted(set(self.kinds) - set(KINDS))
        if bad:
            raise ValueError(f"layer kinds {bad} ({' | '.join(KINDS)})")
        if self.d_inner % self.n_heads:
            raise ValueError(f"d_inner={self.d_inner} not divisible by "
                             f"n_heads={self.n_heads}")

    @property
    def head_dim(self) -> int:
        return self.d_inner // self.n_heads

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.d_state

    @property
    def in_width(self) -> int:
        return 2 * self.d_inner + 2 * self.d_state + self.n_heads

    def layers(self, kind: str) -> Tuple[int, ...]:
        """The model's layers of ``kind``, in order."""
        return tuple(i for i, k in enumerate(self.kinds) if k == kind)

    def index(self, li: int) -> int:
        """Layer ``li``'s place among the layers of its own kind: its
        row in a pool that is stacked over that kind's layers only."""
        return self.kinds[:li].count(self.kinds[li])

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        out["kinds"] = list(self.kinds)
        return out

    @classmethod
    def of(cls, value) -> "HybridSpec":
        """``value`` itself, or the spec a serialized layer carries."""
        return value if isinstance(value, cls) else cls(**dict(value))


# -- the pieces ---------------------------------------------------------------

def project(mha, h, spec: HybridSpec):
    """Normed rows ``h [..., F]`` to ``z [..., H P]``, ``xBC [..., H P
    + 2 N]`` (un-convolved) and ``dt [..., H]`` (raw)."""
    zxbcdt = h @ mha["Win"]
    d, c = spec.d_inner, spec.conv_dim
    return zxbcdt[..., :d], zxbcdt[..., d:d + c], zxbcdt[..., d + c:]


def zero_state(batch: int, spec: HybridSpec, dtype):
    """What an empty context carries: ``(H [B, N, H P] float32, tail
    [B, K - 1, conv_dim] in ``dtype``)``."""
    return (jnp.zeros((batch, spec.d_state, spec.d_inner), jnp.float32),
            jnp.zeros((batch, spec.d_conv - 1, spec.conv_dim),
                      jnp.dtype(dtype)))


def _taps(mha, window):
    """``b + sum_j w_j window[..., j, :]`` then silu, in float32;
    ``window [..., K, C]`` the ``K`` rows that end at the position."""
    w = mha["conv_w"].astype(jnp.float32)               # [K, C]
    y = mha["conv_b"].astype(jnp.float32) + jnp.sum(
        window.astype(jnp.float32) * w, axis=-2)
    return jax.nn.silu(y)


def conv_rows(mha, xbc, tail):
    """One position a row: ``xbc [S, C]`` against each row's carried
    ``tail [S, K - 1, C]``. Returns ``(silu(conv) [S, C] float32, the
    tail after the position)``."""
    with devtime.scope("ops.ssm_conv"):
        window = jnp.concatenate(
            [tail, xbc[:, None].astype(tail.dtype)], axis=1)
        return _taps(mha, window), window[:, 1:]


def conv_chunk(mha, xbc, tail, valid):
    """A chunk of ``T`` positions a sequence: ``xbc [B, T, C]`` after
    the carried ``tail [B, K - 1, C]``; ``valid [B, T]`` marks the
    rows that hold a token, a PREFIX of the chunk (padding follows the
    tokens). Returns ``(silu(conv) [B, T, C] float32, the tail after
    the chunk's last valid row)``: padding moves the tail no more than
    it moves the state."""
    with devtime.scope("ops.ssm_conv"):
        k1 = tail.shape[1]
        t = xbc.shape[1]
        padded = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=1)
        window = jnp.stack([padded[:, j:j + t] for j in range(k1 + 1)],
                           axis=2)                      # [B, T, K, C]
        n = jnp.sum(valid.astype(jnp.int32), axis=1)    # [B]
        keep = n[:, None] + jnp.arange(k1)[None, :]     # rows n .. n+K-2
        new = jnp.take_along_axis(padded, keep[:, :, None], axis=1)
        return _taps(mha, window), new


def step_size(mha, dt):
    """``Delta = softplus(dt + dt_bias)`` float32 and ``A = -exp(A_log)``
    float32 a head."""
    delta = jax.nn.softplus(dt.astype(jnp.float32)
                            + mha["dt_bias"].astype(jnp.float32))
    return delta, -jnp.exp(mha["A_log"].astype(jnp.float32))


def _cols(per_head, p: int):
    """A value a head ``[..., H]`` repeated over its ``P`` feature
    columns ``[..., H P]``."""
    return jnp.repeat(per_head, p, axis=-1)


def ssd_step(x, b, c, delta, a_neg, d_skip, state):
    """One position by the recurrence. ``x [S, H P]``, ``b``/``c``
    ``[S, N]``, ``delta [S, H]`` float32, ``a_neg``/``d_skip`` ``[H]``,
    ``state [S, N, H P]`` float32. Returns ``(y [S, H P] float32,
    state)``."""
    p = x.shape[-1] // delta.shape[-1]
    xf = x.astype(jnp.float32)
    decay = _cols(jnp.exp(delta * a_neg), p)            # [S, H P]
    dx = _cols(delta, p) * xf
    state = (decay[:, None, :] * state
             + b.astype(jnp.float32)[:, :, None] * dx[:, None, :])
    y = jnp.sum(state * c.astype(jnp.float32)[:, :, None], axis=1)
    return y + _cols(d_skip.astype(jnp.float32), p) * xf, state


def ssd_chunk(x, b, c, delta, a_neg, d_skip, valid, state):
    """One chunk of ``T`` positions: inside it the masked ``(C B^T . L)
    X`` product, before it the carried state. ``x [B, T, H P]``,
    ``b``/``c`` ``[B, T, N]``, ``delta [B, T, H]`` float32, ``valid
    [B, T]`` bool (a row that is not valid leaves the state as it was
    and its output means nothing: a state has no causal shelter from
    padding), ``state [B, N, H P]`` float32. Returns ``(y [B, T, H P]
    float32, the state after the chunk's last valid row)``."""
    bsz, t, hp = x.shape
    n_heads = delta.shape[-1]
    p = hp // n_heads
    xf, bf, cf = (v.astype(jnp.float32) for v in (x, b, c))
    delta = jnp.where(valid[..., None], delta, 0.0)     # a = 1, no input
    cum = jnp.cumsum(delta * a_neg, axis=1)             # [B, T, H] <= 0
    scores = jnp.einsum("btn,bjn->btj", cf, bf, precision=_HIGHEST)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    ct = cum.transpose(0, 2, 1)                         # [B, H, T]
    decay = jnp.exp(jnp.where(causal, ct[..., :, None] - ct[..., None, :],
                              -jnp.inf))                # [B, H, T, T]
    w = scores[:, None] * decay * delta.transpose(0, 2, 1)[:, :, None, :]
    xh = xf.reshape(bsz, t, n_heads, p)
    y = jnp.einsum("bhtj,bjhp->bthp", w, xh, precision=_HIGHEST)
    carry = jnp.exp(cum)                                # [B, T, H]
    before = jnp.einsum("btn,bnc->btc", cf, state, precision=_HIGHEST)
    y = y + carry[..., None] * before.reshape(bsz, t, n_heads, p)
    y = y + d_skip.astype(jnp.float32)[:, None] * xh
    tail = jnp.exp(cum[:, -1:] - cum) * delta           # [B, T, H]
    state = (_cols(carry[:, -1], p)[:, None, :] * state
             + jnp.einsum("bjn,bjc->bnc", bf, _cols(tail, p) * xf,
                          precision=_HIGHEST))
    return y.reshape(bsz, t, hp), state


def gated_norm(mha, y, z, eps: float):
    """``RMSNorm(y * silu(z))`` over all ``H P`` features with the
    mixer's own gain: the gate BEFORE the norm, in float32, returned
    in ``z``'s dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    return (g * jax.lax.rsqrt(ms + eps)
            * mha["norm_gamma"].astype(jnp.float32)).astype(z.dtype)


# -- a mixer's rows, by the form the caller's cache wants --------------------

def split(xbc, spec: HybridSpec):
    d, n = spec.d_inner, spec.d_state
    return xbc[..., :d], xbc[..., d:d + n], xbc[..., d + n:]


def mixer_rows(mha, h, spec: HybridSpec, state, tail, update=None):
    """One position a row, ``h [S, F]``: the mixer's output before
    ``Wo`` ``[S, H P]`` and what the rows carry on. ``update`` takes
    the recurrence's place (the paged step's kernel over its pool:
    ``update(x, b, c, delta, a_neg, d_skip) -> (y, state)``, where
    ``state`` is whatever the caller keeps its states in and is handed
    back as it comes)."""
    z, xbc, dt = project(mha, h, spec)
    conv, tail = conv_rows(mha, xbc, tail)
    x, b, c = split(conv, spec)
    delta, a_neg = step_size(mha, dt)
    if update is None:
        with devtime.scope("ops.ssm_decode"):
            y, state = ssd_step(x, b, c, delta, a_neg, mha["D"], state)
    else:
        y, state = update(x, b, c, delta, a_neg, mha["D"])
    return gated_norm(mha, y, z, spec.norm_eps), state, tail


def mixer_chunk(mha, h, spec: HybridSpec, valid, state, tail):
    """A chunk of positions a sequence, ``h [B, T, F]`` with ``T`` at
    most ``spec.chunk``-sized pieces walked in order (a longer ``T``
    runs as a scan over chunks carrying state and tail): the mixer's
    output before ``Wo`` ``[B, T, H P]`` and what the sequence carries
    on after its last valid row."""
    bsz, t, _ = h.shape
    z, xbc, dt = project(mha, h, spec)
    with devtime.scope("ops.ssm_prefill"):
        conv, tail = conv_chunk(mha, xbc, tail, valid)
        x, b, c = split(conv, spec)
        delta, a_neg = step_size(mha, dt)
        size = min(spec.chunk, t)
        if t == size:
            y, state = ssd_chunk(x, b, c, delta, a_neg, mha["D"], valid,
                                 state)
        else:
            n = -(-t // size)
            pad = n * size - t

            def pieces(v):
                v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                return v.reshape(bsz, n, size, *v.shape[2:]).swapaxes(0, 1)

            def body(state, xs):
                y, state = ssd_chunk(*xs[:4], a_neg, mha["D"], xs[4],
                                     state)
                return state, y

            state, ys = jax.lax.scan(body, state, tuple(
                pieces(v) for v in (x, b, c, delta, valid)))
            y = ys.swapaxes(0, 1).reshape(bsz, n * size, -1)[:, :t]
    return gated_norm(mha, y, z, spec.norm_eps), state, tail

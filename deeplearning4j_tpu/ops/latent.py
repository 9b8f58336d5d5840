"""Multi-head latent attention (MLA, DeepSeek-V2/V3) in plain ``jnp``:
the mathematics every caller shares. The training layer
(``nn/layers/attention.py::LatentAttention``), the bucket prefill
(``nn/decoder_infer.py::latent_prefill``) and the paged decode step
(``serving/kv_pager.py::PagedLatent``) differ only in where a
position's latent row goes and how it is read back.

Per position, ``h`` the normed input of width F, H heads:

- ``c_q = RMSNorm(h Wqa)`` (``q_rank`` wide); ``q = c_q Wqb``: H heads
  of ``[q_nope (nope) | q_rope (rope)]``;
- ``[c_kv | k_rope] = h Wkva`` (``kv_rank + rope``); ``c_kv <-
  RMSNorm(c_kv)``; ``k_rope`` is ONE key shared by all heads;
- rotary positions on ``q_rope`` and ``k_rope``, ADJACENT features
  (2i, 2i+1) paired, with YaRN frequencies
  (``ops.rotary.yarn_inv_freq`` over the ``rope`` features);
- the position's **latent row** is ``[c_kv (normed) | k_rope
  (rotated)]``, ``kv_rank + rope`` values: all a cache keeps;
- ``[k_nope | v] = c_kv Wkvb`` (H heads of ``nope + v``); scores
  ``(q_nope . k_nope + q_rope . k_rope) * scale``
  (:func:`softmax_scale`), causal softmax in float32, ``a = softmax
  . v``.

The **expanded** form (:func:`expand`, then
``nn.layers.attention.latent_attention_expanded``) makes K and V of
every position: prefill and training. The **absorbed** form
(:func:`absorb`, :func:`unabsorb`) folds ``Wkvb``'s key half into the
query and applies its value half after the weighted sum of latents, so
a decode step reads latent rows as they are stored: ``q_abs . c_kv =
q_nope . k_nope`` exactly, in exact arithmetic.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import fused_norms
from deeplearning4j_tpu.ops.rotary import yarn_inv_freq


def lanes(n: int) -> int:
    """``n`` values rounded up to whole 128-lane tiles: what a row
    takes in the TPU's memory, and what a kernel may slice."""
    return -(-n // 128) * 128


@dataclass(frozen=True)
class LatentSpec:
    """The sizes of a latent-attention mixer, as a model's published
    configuration names them. ``yarn`` is ``(factor, original_max,
    beta_fast, beta_slow, mscale, mscale_all_dim)`` or None for plain
    rotary frequencies."""
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    yarn: Optional[Tuple[float, ...]] = None

    @property
    def row(self) -> int:
        """Values a cached position holds in one layer."""
        return self.kv_rank + self.rope

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def of(cls, value) -> "LatentSpec":
        """``value`` itself, or the spec a serialized layer carries."""
        if isinstance(value, cls):
            return value
        value = dict(value)
        if value.get("yarn") is not None:
            value["yarn"] = tuple(value["yarn"])
        return cls(**value)


def softmax_scale(spec: LatentSpec) -> float:
    """``(nope + rope)^-1/2 * m^2``, ``m = 0.1 mscale_all_dim
    ln(factor) + 1`` under YaRN (1 without)."""
    scale = (spec.nope + spec.rope) ** -0.5
    if spec.yarn is not None and spec.yarn[5]:
        m = 0.1 * spec.yarn[5] * math.log(spec.yarn[0]) + 1.0
        scale *= m * m
    return scale


def rotate(x, ang):
    """Rotary turn of ``x [..., rope]`` by ``ang [..., rope / 2]``
    (broadcast against ``x``'s leading axes), features (2i, 2i+1)
    paired; the angle math is float32, the product ``x``'s dtype."""
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     axis=-1).reshape(x.shape)


def _rms(x, gamma):
    return fused_norms.rms_norm(x, gamma)   # eps 1e-6, the source's


def project(mha, h, spec: LatentSpec, n_heads: int, theta: float, pos):
    """Rows ``h [N, F]`` at positions ``pos [N]``: ``q_nope [N, H,
    nope]``, ``q_rope [N, H, rope]`` (rotated) and the rows' latent
    ``row [N, kv_rank + rope]`` (normed latent, rotated key)."""
    n = h.shape[0]
    q = (_rms(h @ mha["Wqa"], mha["qa_gamma"]) @ mha["Wqb"]).reshape(
        n, n_heads, spec.nope + spec.rope)
    kva = h @ mha["Wkva"]
    inv_freq = jnp.asarray(yarn_inv_freq(spec.rope, theta, spec.yarn))
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    row = jnp.concatenate(
        [_rms(kva[:, :spec.kv_rank], mha["kv_gamma"]),
         rotate(kva[:, spec.kv_rank:], ang)], axis=-1)
    return (q[..., :spec.nope],
            rotate(q[..., spec.nope:], ang[:, None, :]), row)


def _wkvb(mha, spec: LatentSpec, n_heads: int):
    return mha["Wkvb"].reshape(spec.kv_rank, n_heads, spec.nope + spec.v)


def expand(mha, row, spec: LatentSpec, n_heads: int):
    """Latent rows ``[..., kv_rank + rope]`` to every head's
    ``k_nope [..., H, nope]`` and ``v [..., H, v]``."""
    kv = (row[..., :spec.kv_rank] @ mha["Wkvb"]).reshape(
        *row.shape[:-1], n_heads, spec.nope + spec.v)
    return kv[..., :spec.nope], kv[..., spec.nope:]


def absorb(mha, q_nope, q_rope, spec: LatentSpec):
    """The query against stored latent rows: ``[q_nope Wk^T | q_rope]``
    ``[N, H, kv_rank + rope]``, ``Wk`` the key half of ``Wkvb``."""
    wk = _wkvb(mha, spec, q_nope.shape[1])[..., :spec.nope]
    return jnp.concatenate(
        [jnp.einsum("nhd,chd->nhc", q_nope, wk), q_rope], axis=-1)


def unabsorb(mha, o_lat, spec: LatentSpec):
    """The weighted sums of latents ``[N, H, kv_rank]`` through the
    value half of ``Wkvb``: the mixer's output ``[N, H * v]``."""
    wv = _wkvb(mha, spec, o_lat.shape[1])[..., spec.nope:]
    return jnp.einsum("nhc,chd->nhd", o_lat, wv).reshape(
        o_lat.shape[0], -1)


def attend_rows(q, rows, n_live, scale: float, kv_rank: int):
    """The absorbed form against gathered latent rows, plain: ``q
    [S, H, W]``, ``rows [S, T, W]`` in position order, ``n_live [S]``
    live positions (0: an inactive slot, whose output is zeros) ->
    ``[S, H, kv_rank]``. Float32 scores and softmax."""
    s = jnp.einsum("shw,stw->sht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(rows.shape[1])[None, None, :] < n_live[:, None, None]
    w = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    o = jnp.einsum("sht,stc->shc", w.astype(rows.dtype),
                   rows[..., :kv_rank])
    return jnp.where((n_live > 0)[:, None, None], o, jnp.zeros_like(o))

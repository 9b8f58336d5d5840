"""Rotary frequencies and the RULE by which a softmax layer turns its
queries and keys: what the latent mixer (``ops/latent.py``) and a
softmax layer whose kinds rotate differently (``nn/decoder_infer.py``,
``nn/layers/attention.py``) both read. Plain ``numpy``: frequencies
are constants of a traced program.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np


def yarn_inv_freq(dim: int, theta: float,
                  yarn: Optional[Tuple[float, ...]] = None) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies of ``dim`` rotated features.
    Under YaRN (``yarn = (factor, original_max, beta_fast, beta_slow,
    ...)``) each is a blend of the original frequency and the one
    interpolated by ``factor``: a linear ramp over the correction
    range between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times within the original context (frequencies
    faster than the first keep their value, slower than the second are
    divided by ``factor``)."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return (1.0 / pos_freqs).astype(np.float32)
    factor, original, beta_fast, beta_slow = yarn[:4]

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp       # 1: the original frequency is kept
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - keep) + (
        1.0 / pos_freqs) * keep
    return inv.astype(np.float32)


@dataclass(frozen=True)
class RopeRule:
    """How ONE kind of softmax layer rotates, where a decoder's kinds
    differ in it: base ``theta``; ``rotary_dim``, the leading features
    of a head that turn (feature ``i`` with ``i + rotary_dim / 2``; the
    others are left as they are; None: the whole head); ``yarn``
    ``(factor, original_max, beta_fast, beta_slow)`` or None for plain
    frequencies; ``factor`` multiplies cos and sin both (a published
    ``attention_factor``). Data of ``dims``: a decoder with ONE rotary
    rule gives its ``rope_theta`` and no rule."""
    theta: float
    rotary_dim: Optional[int] = None
    yarn: Optional[Tuple[float, ...]] = None
    factor: float = 1.0

    def __post_init__(self):
        if self.yarn is not None:
            object.__setattr__(self, "yarn", tuple(self.yarn))
        if self.rotary_dim is not None and self.rotary_dim % 2:
            raise ValueError(f"rotary_dim={self.rotary_dim} is odd")

    def inv_freq(self, head_dim: int) -> np.ndarray:
        """The rule's frequencies for heads ``head_dim`` wide."""
        return yarn_inv_freq(self.rotary_dim or head_dim, self.theta,
                             self.yarn)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def of(cls, value) -> "RopeRule":
        return value if isinstance(value, cls) else cls(**dict(value))

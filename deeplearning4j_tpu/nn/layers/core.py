"""Core feed-forward layers.

Reference classes (deeplearning4j-nn):
  org.deeplearning4j.nn.conf.layers.DenseLayer / OutputLayer / LossLayer /
  ActivationLayer / DropoutLayer / EmbeddingLayer / EmbeddingSequenceLayer /
  ElementWiseMultiplicationLayer / BatchNormalization /
  LocalResponseNormalization; impls under org.deeplearning4j.nn.layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn import weights as winit
from deeplearning4j_tpu.ops import losses as losses_mod


@register_layer
@dataclass
class DenseLayer(Layer):
    """Fully connected layer (reference DenseLayer; cuDNN-free matmul —
    lands directly on the MXU). Supports the reference's ``hasLayerNorm``
    option (DenseLayer.Builder.hasLayerNorm)."""
    n_in: Optional[int] = None
    n_out: int = 0
    has_layer_norm: bool = False
    has_bias: bool = True

    def init(self, key, input_shape, dtype=jnp.float32):
        n_in = self.n_in or int(math.prod(input_shape))
        kW, = jax.random.split(key, 1)
        params = {"W": winit.get(self.weight_init or "xavier")(
            kW, (n_in, self.n_out), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        if self.has_layer_norm:
            params["g"] = jnp.ones((self.n_out,), dtype)
        return params, {}, (self.n_out,)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if x.ndim > 2:
            x = x.reshape(x.shape[0], -1)
        z = x @ params["W"]
        if self.has_layer_norm:
            mu = jnp.mean(z, axis=-1, keepdims=True)
            var = jnp.var(z, axis=-1, keepdims=True)
            z = params["g"] * (z - mu) / jnp.sqrt(var + 1e-5)
        if self.has_bias:
            z = z + params["b"]
        y = self._act()(z)
        return self._maybe_dropout(y, train, rng), state


@register_layer
@dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (reference OutputLayer extends BaseOutputLayer).

    ``loss`` names a function in ``ops.losses``; scoring happens in the
    network's train step, where the loss is applied to this layer's
    activations (with from_logits fusion when activation is softmax —
    see MultiLayerNetwork._loss_of).
    """
    loss: str = "mcxent"


@register_layer
@dataclass
class LossLayer(Layer):
    """Loss-only layer, no params (reference LossLayer)."""
    loss: str = "mse"

    def init(self, key, input_shape, dtype=jnp.float32):
        return {}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._act()(x), state

    def has_params(self):
        return False


@register_layer
@dataclass
class ActivationLayer(Layer):
    """Stateless activation (reference ActivationLayer)."""

    def init(self, key, input_shape, dtype=jnp.float32):
        return {}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._act()(x), state

    def has_params(self):
        return False


@register_layer
@dataclass
class DropoutLayer(Layer):
    """Standalone dropout (reference DropoutLayer). ``dropout`` is the
    drop probability; inverted dropout (scale at train time)."""

    def __post_init__(self):
        if self.dropout is None:
            self.dropout = 0.5

    def init(self, key, input_shape, dtype=jnp.float32):
        return {}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._maybe_dropout(x, train, rng), state

    def has_params(self):
        return False


@register_layer
@dataclass
class EmbeddingLayer(Layer):
    """Int index -> dense vector (reference EmbeddingLayer; one index per
    example). A gather — XLA lowers to a dynamic-slice, TPU-friendly."""
    n_in: Optional[int] = None     # vocab size
    n_out: int = 0
    has_bias: bool = False
    #: the looked-up rows times this (a decoder's published
    #: ``embedding_multiplier``); None: as they are
    multiplier: Optional[float] = None

    def init(self, key, input_shape, dtype=jnp.float32):
        params = {"W": winit.get(self.weight_init or "xavier")(
            key, (self.n_in, self.n_out), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}, (self.n_out,)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = params["W"][idx]
        if self.multiplier is not None:
            y = y * jnp.asarray(self.multiplier, y.dtype)
        if self.has_bias:
            y = y + params["b"]
        return self._act()(y), state


@register_layer
@dataclass
class EmbeddingSequenceLayer(EmbeddingLayer):
    """Sequence of indices [B,T] -> [B,T,F] (reference
    EmbeddingSequenceLayer)."""
    input_length: Optional[int] = None

    def init(self, key, input_shape, dtype=jnp.float32):
        params, state, _ = super().init(key, input_shape, dtype)
        t = self.input_length or (input_shape[0] if input_shape else None)
        return params, state, (t, self.n_out)


@register_layer
@dataclass
class ElementWiseMultiplicationLayer(Layer):
    """out = activation(in ⊙ w + b) (reference
    ElementWiseMultiplicationLayer)."""
    n_out: int = 0

    def init(self, key, input_shape, dtype=jnp.float32):
        n = self.n_out or input_shape[-1]
        params = {"W": jnp.ones((n,), dtype),
                  "b": jnp.full((n,), self.bias_init, dtype)}
        return params, {}, (n,)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self._act()(x * params["W"] + params["b"]), state


@register_layer
@dataclass
class BatchNormalization(Layer):
    """Batch norm over the trailing feature/channel axis (reference
    BatchNormalization + CudnnBatchNormalizationHelper; here one fused
    XLA graph, running stats carried in ``state``)."""
    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False

    def init(self, key, input_shape, dtype=jnp.float32):
        c = input_shape[-1]
        params = {} if self.lock_gamma_beta else {
            "gamma": jnp.ones((c,), dtype), "beta": jnp.zeros((c,), dtype)}
        state = {"mean": jnp.zeros((c,), dtype),
                 "var": jnp.ones((c,), dtype)}
        return params, state, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        axes = tuple(range(x.ndim - 1))
        sdt = jnp.promote_types(x.dtype, jnp.float32)  # f64 stays f64
        if train:
            xf = x.astype(sdt)
            mu = jnp.mean(xf, axis=axes)
            if x.dtype in (jnp.bfloat16, jnp.float16):
                # one-pass batch stats: E[x] and E[x²] reduce together
                # in a single fused multi-output reduction (jnp.var
                # walks x twice and materialises x-mu — ~25% of a
                # ResNet-50 step went to those reductions). Safe here:
                # a half-precision input with |mean|≫std carries no var
                # information in EITHER formulation, and the squares
                # accumulate in fp32.
                var = (jnp.mean(jnp.square(xf), axis=axes)
                       - jnp.square(mu))
                var = jnp.maximum(var, 0.0)
            else:
                # full precision: shifted two-pass, immune to the
                # catastrophic cancellation of E[x²]−E[x]²
                var = jnp.mean(jnp.square(xf - mu), axis=axes)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mu,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mu = state["mean"].astype(sdt)
            var = state["var"].astype(sdt)
            new_state = state
        # fold into one fused multiply-add over the big tensor:
        # y = a·x + b with per-channel a, b
        inv = jax.lax.rsqrt(var + self.eps)
        if not self.lock_gamma_beta:
            inv = inv * params["gamma"].astype(sdt)
            b = params["beta"].astype(sdt) - mu * inv
        else:
            b = -mu * inv
        y = x * inv.astype(x.dtype) + b.astype(x.dtype)
        return self._act()(y), new_state

    def has_params(self):
        return not self.lock_gamma_beta


@register_layer
@dataclass
class LayerNormalization(Layer):
    """Layer norm over the trailing axis. The reference exposes this as
    DenseLayer.hasLayerNorm / SameDiff ``standardize``; standalone layer
    added for the transformer stack."""
    eps: float = 1e-5

    def init(self, key, input_shape, dtype=jnp.float32):
        c = input_shape[-1]
        return ({"gamma": jnp.ones((c,), dtype),
                 "beta": jnp.zeros((c,), dtype)}, {}, tuple(input_shape))

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # platform-helper dispatch (ops/fused_norms.py): fused Pallas
        # LayerNorm on TPU, the exact pre-existing XLA expression
        # otherwise (gate-off programs byte-identical)
        from deeplearning4j_tpu.ops import fused_norms
        return fused_norms.layer_norm(x, params["gamma"],
                                      params["beta"],
                                      eps=self.eps), state


#: default RMSNorm epsilon — zoo/gpt.py's KV-cache decode re-derives
#: the norm inline and MUST use the same value (kept in one place)
RMSNORM_EPS = 1e-6


@register_layer
@dataclass
class RMSNorm(Layer):
    """Root-mean-square norm over the trailing axis (no mean
    subtraction, no bias) — the modern-LM normalisation the causal
    transformer stack uses. No reference counterpart (its transformer
    support predates RMSNorm); provided for the native LM family."""
    eps: float = RMSNORM_EPS
    #: the normed rows times this (a decoder's final norm under a
    #: published ``logits_scaling`` s takes 1 / s); None: as they are
    multiplier: Optional[float] = None

    def init(self, key, input_shape, dtype=jnp.float32):
        c = input_shape[-1]
        return {"gamma": jnp.ones((c,), dtype)}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # platform-helper dispatch (ops/fused_norms.py): fused Pallas
        # RMSNorm on TPU, the exact pre-existing XLA expression
        # otherwise (gate-off programs byte-identical)
        from deeplearning4j_tpu.ops import fused_norms
        y = fused_norms.rms_norm(x, params["gamma"], eps=self.eps)
        if self.multiplier is not None:
            y = y * jnp.asarray(self.multiplier, y.dtype)
        return y, state


@register_layer
@dataclass
class LocalResponseNormalization(Layer):
    """LRN across channels (reference LocalResponseNormalization —
    AlexNet-era). Channels-last."""
    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def init(self, key, input_shape, dtype=jnp.float32):
        return {}, {}, tuple(input_shape)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        sq = jnp.square(x)
        half = self.n // 2
        pads = [(0, 0)] * (x.ndim - 1) + [(half, half)]
        padded = jnp.pad(sq, pads)
        # sliding-window sum over channel axis via cumsum difference
        cs = jnp.cumsum(padded, axis=-1)
        zeros = jnp.zeros_like(cs[..., :1])
        cs = jnp.concatenate([zeros, cs], axis=-1)
        win = cs[..., self.n:] - cs[..., :-self.n]
        denom = jnp.power(self.k + self.alpha * win, self.beta)
        return x / denom, state

    def has_params(self):
        return False


@register_layer
@dataclass
class CnnLossLayer(LossLayer):
    """Per-position loss over [B, H, W, C] feature maps (reference
    CnnLossLayer — segmentation-style heads where every spatial
    position carries a label). Loss machinery is the network's
    (labels shaped like the activations); this layer applies the
    activation only."""


@register_layer
@dataclass
class Cnn3DLossLayer(LossLayer):
    """Reference Cnn3DLossLayer — [B, D, H, W, C] per-position loss."""

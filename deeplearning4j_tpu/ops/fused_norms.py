"""Fused norm / residual epilogue kernels (Pallas, fwd + bwd).

The round-5 device-time observatory (``obs/devtime.py``,
``gap_report()``) named the normalisation epilogues as
``pallas_candidate`` scopes: every RMSNorm/LayerNorm in the layer
stack lowers to a chain of small VPU ops (square, reduce, rsqrt,
broadcast-multiply) that XLA schedules as separate passes over the
activation — low roofline utilization on a tensor the adjacent matmul
already streamed through VMEM. The cuDNN-primitives shape of the win
(PAPERS.md: arxiv 1410.0759): a SMALL library of tuned fused
primitives behind the existing layer API, dispatched platform-helper
style (``nn/layers/attention.py::_use_flash`` is the pattern).

Kernels (each: one VMEM pass fwd, one recompute-style pass bwd, the
cross-row ``dgamma``/``dbeta`` reductions accumulated across the
sequential TPU grid):

- :func:`rms_norm` — RMSNorm over the trailing axis. Dispatched from
  ``nn.layers.core.RMSNorm`` and ``nn.decoder_infer.rms`` (train blocks AND
  the KV-cached decode/prefill paths).
- :func:`add_rms_norm` — residual add + RMSNorm in one pass,
  returning ``(normed, summed)`` — the pre-norm transformer block's
  ``x = x + attn; h = rms(x)`` epilogue
  (``nn.layers.attention.TransformerDecoderBlock``).
- :func:`layer_norm` — LayerNorm (mean subtraction + bias) over the
  trailing axis, dispatched from ``nn.layers.core.LayerNormalization``
  (and through it the encoder block stack).

Dispatch contract (ARCHITECTURE.md §17): the gate decides at TRACE
time. Gate OFF returns the *exact* jnp expression the layers used
before this module existed — same ops in the same order, so the
compiled program is byte-identical (fenced in
tests/test_fused_kernels.py). Gate ON requires a TPU backend — or
``DL4J_TPU_KERNEL_FORCE=1``, which forces the kernel path in Pallas
interpret mode so CPU CI exercises the dispatch decision itself (the
``environment.py`` flag the testability satellite of ISSUE 15 added).
Every kernel's device time lands under its own ``devtime.scope``
(``ops.rms_norm`` / ``ops.add_rms_norm`` / ``ops.layer_norm``) and is
declared in ``ops/kernel_registry.py`` with its fallback + parity
test, which is how ``gap_report()`` marks the norm scopes ``closed``.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops.pallas_kernels import (_interpret,
                                                   _jnp_fallback)

#: default trailing-axis epsilon — numerically the same constant as
#: ``nn.layers.core.RMSNORM_EPS`` (kept literal here: the layer stack
#: imports THIS module, so importing the layer constant back would
#: cycle); callers always pass their layer's eps explicitly.
RMSNORM_EPS = 1e-6
LAYERNORM_EPS = 1e-5

#: VMEM budget per operand block (bytes of f32): bounds block_rows at
#: large feature dims so the row block + its f32 upcast stay resident
_BLOCK_BYTES = 2 * 1024 * 1024


def _use_fused(x, *params) -> bool:
    """The dispatch gate, decided at trace time. TPU dispatches the
    kernel (features ≥ ``DL4J_TPU_FUSED_NORM_MIN_F`` — tiny rows would
    pad to a full 128-lane block for no bandwidth win); CPU/old-jaxlib
    falls back to the XLA expression value-for-value;
    ``DL4J_TPU_KERNEL_FORCE`` forces the kernel in interpret mode so
    CI covers the dispatch decision. float64 (gradient checking) and
    shard_map-manual-axes-on-CPU (interpret can't run there — the
    flash kernels' rule) always fall back."""
    from deeplearning4j_tpu.environment import get_flag
    if x.ndim < 2 or x.dtype == jnp.float64:
        return False
    if _jnp_fallback(x, *params):
        return False
    if get_flag("DL4J_TPU_KERNEL_FORCE"):
        return True
    return (jax.default_backend() == "tpu"
            and x.shape[-1] >= get_flag("DL4J_TPU_FUSED_NORM_MIN_F"))


def _blocks(r: int, f: int) -> Tuple[int, int, int]:
    """(block_rows, padded_rows, padded_features): features lane-align
    to 128, rows sublane-align to 8, block_rows bounded by the VMEM
    budget (Mosaic wants the last two block dims (8, 128)-divisible or
    equal to the array dims)."""
    fp = max(128, -(-f // 128) * 128)
    br = max(8, min(256, (_BLOCK_BYTES // (fp * 4)) // 8 * 8))
    br = min(br, -(-r // 8) * 8)
    rp = -(-r // br) * br
    return br, rp, fp


def _pad2(x, rp: int, fp: int):
    return jnp.pad(x, ((0, rp - x.shape[0]), (0, fp - x.shape[1])))


def _pad_vec(v, fp: int):
    return jnp.pad(v, (0, fp - v.shape[0])).reshape(1, fp)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, g_ref, o_ref, *, eps: float, f_real: int):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.sum(x * x, axis=-1, keepdims=True) / f_real
    rstd = lax.rsqrt(ms + eps)
    o_ref[...] = (x * rstd
                  * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_bwd_kernel(x_ref, g_ref, do_ref, dx_ref, dg_ref, *,
                    eps: float, f_real: int):
    # dgamma accumulates across the sequential row-block grid; the
    # recompute of rstd from the x block (FlashAttention-style) saves
    # writing/reading a per-row residual through HBM
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dg_ref[...] = jnp.zeros_like(dg_ref)

    x = x_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    gam = g_ref[...].astype(jnp.float32)
    ms = jnp.sum(x * x, axis=-1, keepdims=True) / f_real
    rstd = lax.rsqrt(ms + eps)
    gg = do * gam
    c = jnp.sum(gg * x, axis=-1, keepdims=True) / f_real
    dx_ref[...] = ((gg - x * (c * rstd * rstd)) * rstd).astype(
        dx_ref.dtype)
    dg_ref[...] += jnp.sum(do * x * rstd, axis=0, keepdims=True)


def _rms_fwd_call(x2, gamma, eps: float):
    r, f = x2.shape
    br, rp, fp = _blocks(r, f)
    out = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps, f_real=f),
        out_shape=jax.ShapeDtypeStruct((rp, fp), x2.dtype),
        grid=(rp // br,),
        in_specs=[pl.BlockSpec((br, fp), lambda i: (i, 0)),
                  pl.BlockSpec((1, fp), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, fp), lambda i: (i, 0)),
        interpret=_interpret(),
    )(_pad2(x2, rp, fp), _pad_vec(gamma, fp))
    return out[:r, :f]


def _rms_bwd_call(x2, gamma, do2, eps: float):
    r, f = x2.shape
    br, rp, fp = _blocks(r, f)
    dx, dg = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps, f_real=f),
        out_shape=(jax.ShapeDtypeStruct((rp, fp), x2.dtype),
                   jax.ShapeDtypeStruct((1, fp), jnp.float32)),
        grid=(rp // br,),
        in_specs=[pl.BlockSpec((br, fp), lambda i: (i, 0)),
                  pl.BlockSpec((1, fp), lambda i: (0, 0)),
                  pl.BlockSpec((br, fp), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, fp), lambda i: (i, 0)),
                   pl.BlockSpec((1, fp), lambda i: (0, 0))),
        interpret=_interpret(),
    )(_pad2(x2, rp, fp), _pad_vec(gamma, fp), _pad2(do2, rp, fp))
    return dx[:r, :f], dg[0, :f].astype(gamma.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms(x2, gamma, eps):
    return _rms_fwd_call(x2, gamma, eps)


def _rms_vjp_fwd(x2, gamma, eps):
    return _rms_fwd_call(x2, gamma, eps), (x2, gamma)


def _rms_vjp_bwd(eps, res, g):
    x2, gamma = res
    return _rms_bwd_call(x2, gamma, g, eps)


_rms.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


def rms_norm_reference(x, gamma, eps: float = RMSNORM_EPS):
    """The XLA fallback — EXACTLY the expression
    ``nn.layers.core.RMSNorm`` / ``nn.decoder_infer.rms`` used before this
    module existed (same ops, same order: the gate-off program is
    byte-identical, fenced in tests/test_fused_kernels.py)."""
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * gamma


def rms_norm(x, gamma, eps: float = RMSNORM_EPS):
    """RMSNorm over the trailing axis, platform-helper dispatched:
    Pallas fused fwd+bwd on TPU (or under ``DL4J_TPU_KERNEL_FORCE``
    in interpret mode), :func:`rms_norm_reference` everywhere else."""
    if not _use_fused(x, gamma):
        return rms_norm_reference(x, gamma, eps)
    from deeplearning4j_tpu.obs import devtime
    with devtime.scope("ops.rms_norm"):
        shape = x.shape
        y = _rms(x.reshape(-1, shape[-1]), gamma, float(eps))
        return y.reshape(shape)


# ---------------------------------------------------------------------------
# residual add + RMSNorm (the pre-norm block epilogue)
# ---------------------------------------------------------------------------

def _add_rms_fwd_kernel(x_ref, d_ref, g_ref, o_ref, s_ref, *,
                        eps: float, f_real: int):
    s = x_ref[...].astype(jnp.float32) + d_ref[...].astype(jnp.float32)
    s_ref[...] = s.astype(s_ref.dtype)
    ms = jnp.sum(s * s, axis=-1, keepdims=True) / f_real
    rstd = lax.rsqrt(ms + eps)
    o_ref[...] = (s * rstd
                  * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _add_rms_fwd_call(x2, d2, gamma, eps: float):
    r, f = x2.shape
    br, rp, fp = _blocks(r, f)
    y, s = pl.pallas_call(
        functools.partial(_add_rms_fwd_kernel, eps=eps, f_real=f),
        out_shape=(jax.ShapeDtypeStruct((rp, fp), x2.dtype),
                   jax.ShapeDtypeStruct((rp, fp), x2.dtype)),
        grid=(rp // br,),
        in_specs=[pl.BlockSpec((br, fp), lambda i: (i, 0)),
                  pl.BlockSpec((br, fp), lambda i: (i, 0)),
                  pl.BlockSpec((1, fp), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((br, fp), lambda i: (i, 0)),
                   pl.BlockSpec((br, fp), lambda i: (i, 0))),
        interpret=_interpret(),
    )(_pad2(x2, rp, fp), _pad2(d2, rp, fp), _pad_vec(gamma, fp))
    return y[:r, :f], s[:r, :f]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _add_rms(x2, d2, gamma, eps):
    return _add_rms_fwd_call(x2, d2, gamma, eps)


def _add_rms_vjp_fwd(x2, d2, gamma, eps):
    y, s = _add_rms_fwd_call(x2, d2, gamma, eps)
    return (y, s), (s, gamma)


def _add_rms_vjp_bwd(eps, res, ct):
    # d(x + delta) is shared: the norm's dx (recomputed from the saved
    # sum via the rms bwd kernel) plus the residual stream's own
    # cotangent flows identically into both addends
    s, gamma = res
    dy, ds = ct
    dxs, dg = _rms_bwd_call(s, gamma, dy, eps)
    dtot = dxs + ds.astype(dxs.dtype)
    return dtot, dtot, dg


_add_rms.defvjp(_add_rms_vjp_fwd, _add_rms_vjp_bwd)


def add_rms_norm_reference(x, delta, gamma, eps: float = RMSNORM_EPS):
    """Fallback: the unfused residual-then-norm pair, exactly as the
    pre-norm decoder block wrote it (``x = x + delta`` then the
    :func:`rms_norm_reference` expression)."""
    s = x + delta
    return rms_norm_reference(s, gamma, eps), s


def add_rms_norm(x, delta, gamma, eps: float = RMSNORM_EPS):
    """Residual add + RMSNorm in ONE pass: returns ``(normed,
    summed)`` where ``summed = x + delta`` feeds the block's next
    residual. Fused, the activation streams through VMEM once instead
    of (add write) + (norm read) + (norm write)."""
    if not _use_fused(x, gamma, delta):
        return add_rms_norm_reference(x, delta, gamma, eps)
    from deeplearning4j_tpu.obs import devtime
    with devtime.scope("ops.add_rms_norm"):
        shape = x.shape
        y, s = _add_rms(x.reshape(-1, shape[-1]),
                        delta.reshape(-1, shape[-1]), gamma,
                        float(eps))
        return y.reshape(shape), s.reshape(shape)


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float,
                   f_real: int):
    x = x_ref[...].astype(jnp.float32)
    # padded lanes carry zeros, which would bias the centered moments —
    # mask them out of xc so mean/var divide by the REAL feature count
    colmask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < f_real
    mu = jnp.sum(x, axis=-1, keepdims=True) / f_real
    xc = jnp.where(colmask, x - mu, 0.0)
    var = jnp.sum(xc * xc, axis=-1, keepdims=True) / f_real
    y = xc / jnp.sqrt(var + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_bwd_kernel(x_ref, g_ref, do_ref, dx_ref, dg_ref, db_ref, *,
                   eps: float, f_real: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    gam = g_ref[...].astype(jnp.float32)
    colmask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < f_real
    mu = jnp.sum(x, axis=-1, keepdims=True) / f_real
    xc = jnp.where(colmask, x - mu, 0.0)
    var = jnp.sum(xc * xc, axis=-1, keepdims=True) / f_real
    rstd = lax.rsqrt(var + eps)
    xhat = xc * rstd
    gh = do * gam                  # zero on padded lanes (gamma pads 0)
    m1 = jnp.sum(gh, axis=-1, keepdims=True) / f_real
    m2 = jnp.sum(gh * xhat, axis=-1, keepdims=True) / f_real
    dx = (gh - m1 - xhat * m2) * rstd
    dx_ref[...] = jnp.where(colmask, dx, 0.0).astype(dx_ref.dtype)
    dg_ref[...] += jnp.sum(do * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(do, axis=0, keepdims=True)


def _ln_fwd_call(x2, gamma, beta, eps: float):
    r, f = x2.shape
    br, rp, fp = _blocks(r, f)
    out = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps, f_real=f),
        out_shape=jax.ShapeDtypeStruct((rp, fp), x2.dtype),
        grid=(rp // br,),
        in_specs=[pl.BlockSpec((br, fp), lambda i: (i, 0)),
                  pl.BlockSpec((1, fp), lambda i: (0, 0)),
                  pl.BlockSpec((1, fp), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, fp), lambda i: (i, 0)),
        interpret=_interpret(),
    )(_pad2(x2, rp, fp), _pad_vec(gamma, fp), _pad_vec(beta, fp))
    return out[:r, :f]


def _ln_bwd_call(x2, gamma, do2, eps: float):
    r, f = x2.shape
    br, rp, fp = _blocks(r, f)
    dx, dg, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps, f_real=f),
        out_shape=(jax.ShapeDtypeStruct((rp, fp), x2.dtype),
                   jax.ShapeDtypeStruct((1, fp), jnp.float32),
                   jax.ShapeDtypeStruct((1, fp), jnp.float32)),
        grid=(rp // br,),
        in_specs=[pl.BlockSpec((br, fp), lambda i: (i, 0)),
                  pl.BlockSpec((1, fp), lambda i: (0, 0)),
                  pl.BlockSpec((br, fp), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, fp), lambda i: (i, 0)),
                   pl.BlockSpec((1, fp), lambda i: (0, 0)),
                   pl.BlockSpec((1, fp), lambda i: (0, 0))),
        interpret=_interpret(),
    )(_pad2(x2, rp, fp), _pad_vec(gamma, fp), _pad2(do2, rp, fp))
    return (dx[:r, :f], dg[0, :f].astype(gamma.dtype),
            db[0, :f].astype(gamma.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x2, gamma, beta, eps):
    return _ln_fwd_call(x2, gamma, beta, eps)


def _ln_vjp_fwd(x2, gamma, beta, eps):
    return _ln_fwd_call(x2, gamma, beta, eps), (x2, gamma)


def _ln_vjp_bwd(eps, res, g):
    x2, gamma = res
    return _ln_bwd_call(x2, gamma, g, eps)


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


def layer_norm_reference(x, gamma, beta, eps: float = LAYERNORM_EPS):
    """The XLA fallback — EXACTLY
    ``nn.layers.core.LayerNormalization``'s pre-existing expression
    (same ops, same order: gate-off programs are byte-identical)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * gamma + beta


def layer_norm(x, gamma, beta, eps: float = LAYERNORM_EPS):
    """LayerNorm over the trailing axis, platform-helper dispatched
    like :func:`rms_norm` (fused single-pass moments + normalisation;
    bwd recomputes the moments per block and accumulates
    dgamma/dbeta across the row grid)."""
    if not _use_fused(x, gamma, beta):
        return layer_norm_reference(x, gamma, beta, eps)
    from deeplearning4j_tpu.obs import devtime
    with devtime.scope("ops.layer_norm"):
        shape = x.shape
        y = _ln(x.reshape(-1, shape[-1]), gamma, beta, float(eps))
        return y.reshape(shape)


# ---------------------------------------------------------------------------
# bench row (bench.py `fused_kernels` / dossier `fused_epilogues`)
# ---------------------------------------------------------------------------

def fused_kernels_report(rows: int = 2048, feats: int = 512,
                         iters: int = 30):
    """Per-kernel interpret-parity status + fallback timings — the
    ``fused_kernels`` section of ``bench.py`` and the dossier's
    ``fused_epilogues`` entry. On CPU the kernel timings are interpret
    mode (wiring validation, labeled); the parity numbers are the real
    contract — the same kernel code lowers through Mosaic on TPU."""
    import os
    import time

    import numpy as np

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rows, feats)), jnp.float32)
    d = jnp.asarray(rng.standard_normal((rows, feats)), jnp.float32)
    gam = jnp.asarray(rng.standard_normal((feats,)), jnp.float32)
    bet = jnp.asarray(rng.standard_normal((feats,)), jnp.float32)
    co = jnp.asarray(rng.standard_normal((rows, feats)), jnp.float32)

    def timed(fn, *args):
        # operands are jit ARGUMENTS — closed-over constants would
        # let XLA constant-fold part of the program (measured 2.4x
        # skew on the reference norm) and invalidate the
        # kernel-vs-fallback comparison this row exists for
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    def first(t):
        return jax.tree_util.tree_leaves(t)[0]

    # the baseline pass calls the *_reference fallbacks DIRECTLY —
    # toggling the env gate cannot force the fallback on a real TPU
    # (the platform gate dispatches the kernel regardless), and a
    # kernel-vs-kernel comparison would certify parity that was never
    # measured. EVERY operand (incl. the residual delta and beta)
    # rides as a jit argument so neither arm's program constant-folds.
    cases = {
        "rms_norm": (
            rms_norm, rms_norm_reference,
            lambda fn: lambda q, g: jnp.sum(fn(q, g) * co),
            (x, gam), (0, 1)),
        "add_rms_norm": (
            add_rms_norm, add_rms_norm_reference,
            lambda fn: lambda q, dd, g: jnp.sum(fn(q, dd, g)[0] * co),
            (x, d, gam), (0, 1, 2)),
        "layer_norm": (
            layer_norm, layer_norm_reference,
            lambda fn: lambda q, g, b2: jnp.sum(fn(q, g, b2) * co),
            (x, gam, bet), (0, 1, 2)),
    }
    out = {"rows": rows, "features": feats,
           "platform": jax.devices()[0].platform,
           "interpret": _interpret(), "kernels": {}}
    prev = os.environ.get("DL4J_TPU_KERNEL_FORCE")
    try:
        # kernel pass: force the gate so the CPU (interpret) run
        # exercises the kernel path too; reference pass needs no gate
        os.environ["DL4J_TPU_KERNEL_FORCE"] = "1"
        for name, (fwd, ref_fwd, mk_loss, args, anums) in cases.items():
            ref_y = jax.jit(ref_fwd)(*args)
            ref_g = jax.jit(jax.grad(mk_loss(ref_fwd),
                                     argnums=anums))(*args)
            fallback_ms = timed(jax.jit(ref_fwd), *args)
            ker_y = jax.jit(fwd)(*args)
            ker_g = jax.jit(jax.grad(mk_loss(fwd),
                                     argnums=anums))(*args)
            err_f = float(jnp.max(jnp.abs(first(ker_y) - first(ref_y))))
            err_b = max(float(jnp.max(jnp.abs(a - b)))
                        for a, b in zip(ker_g, ref_g))
            rec = {
                "fwd_max_abs_err": err_f,
                "bwd_max_abs_err": err_b,
                "parity": "ok" if (err_f < 1e-4 and err_b < 1e-4)
                else "FAIL",
                "fallback_ms": round(fallback_ms, 3),
            }
            if not _interpret():
                rec["kernel_ms"] = round(timed(jax.jit(fwd), *args), 3)
            out["kernels"][name] = rec
    finally:
        if prev is None:
            os.environ.pop("DL4J_TPU_KERNEL_FORCE", None)
        else:
            os.environ["DL4J_TPU_KERNEL_FORCE"] = prev
    return out

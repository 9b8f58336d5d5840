"""Ring attention — sequence/context parallelism over the ICI ring.

NEW capability vs the reference (SURVEY §5 long-context: the reference's
longest-sequence story is truncated BPTT; its attention ops are
single-device). Required by the rebuild spec for modern sequence
scaling.

Design (blockwise/ring attention à la Liu et al.): the sequence axis is
sharded over the mesh's 'seq' axis. Each device holds a Q block and a
KV block. Over ``n_seq`` ring steps, every device computes flash
attention of its Q block against the KV block it currently holds — one
``ops.pallas_kernels.flash_block_fwd`` call per step, returning the
block's normalised output and per-row logsumexp — then merges the pair
into its running (out, lse) with exact log-sum-exp combination and
rotates the KV block to its ring neighbor with ``jax.lax.ppermute``
(pure ICI traffic, overlapped by XLA with the block kernels). Memory is
O(T/N) per device; no device ever materialises the full [T,T] score
matrix — not even per ring step (the Pallas kernel tiles each block).

Causal masking (``causal=True``): at ring step ``i`` a device with ring
index ``m`` holds the KV block that ORIGINATED on device ``(m - i) mod
n`` — so its global key offset is ``src·T_loc`` while the local query
offset is ``m·T_loc``. Both offsets are passed to the flash kernel,
which masks above the (offset) diagonal and skips blocks entirely above
it without doing any work (the einsum formulation can't skip).

Backward is a second ring (FlashAttention-2 style): each device keeps
its q/out/lse/dO resident and re-rotates KV; per step one
``flash_block_bwd`` call yields the (dq contribution, dk, dv) of that
(q-block, kv-block) pair — dq accumulates locally, while dk/dv
accumulators TRAVEL WITH their kv block around the ring, arriving home
(fully summed over every q block) after n steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from deeplearning4j_tpu.ops.pallas_kernels import (
    flash_block_fwd, flash_block_bwd)


def _merge_blocks(out, lse, o_b, lse_b):
    """Merge a new block's normalised (out, lse) into the running pair.

    Exact: out_b·exp(lse_b) is the block's unnormalised numerator and
    exp(lse_b) its denominator, so the combination reweights by
    exp(lse − lse_new) with lse_new = logaddexp(lse, lse_b)."""
    lse_new = jnp.logaddexp(lse, lse_b)
    safe = jnp.where(jnp.isinf(lse_new), 0.0, lse_new)
    w_old = jnp.where(jnp.isinf(lse), 0.0, jnp.exp(lse - safe))
    w_new = jnp.where(jnp.isinf(lse_b), 0.0, jnp.exp(lse_b - safe))
    return out * w_old + o_b.astype(jnp.float32) * w_new, lse_new


def _ring_perm(n):
    return [(j, (j + 1) % n) for j in range(n)]


def _vary_like(ref_vma, axis_name):
    """Align a freshly-created carry array onto the varying axes of
    the ring operands. Under a single-axis shard_map that is just
    ``axis_name``; under a composed multi-axis mesh (DP×SP×TP — the
    operands arrive varying over 'data'/'tensor' too) the loop carry
    must match the body outputs' full vma set or the fori_loop
    type-check rejects it."""
    axes = set(ref_vma) | {axis_name}

    def vary(x):
        have = getattr(jax.typeof(x), "vma", frozenset())
        missing = tuple(axes - set(have))
        return lax.pcast(x, missing, to="varying") if missing else x
    return vary


def _ring_fwd_impl(q, k, v, km, axis_name, causal, groups):
    """q: [B·H, T_loc, D]; k,v: [B·Hkv, T_loc, D] (GQA: H = Hkv·groups
    — only the SMALL kv travels the ring; the flash kernel shares one
    kv block per head group via its index map, no broadcast);
    km: [B·Hkv, T_loc] or None (None saves the per-step mask ppermute —
    the flash call itself still substitutes an all-ones mask operand).
    Returns (out [B·H, T_loc, D] in q.dtype, lse [B·H, T_loc, 1] f32)."""
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    t = q.shape[1]
    has_km = km is not None
    vary = _vary_like(getattr(jax.typeof(q), "vma", frozenset()),
                      axis_name)
    out0 = vary(jnp.zeros(q.shape, jnp.float32))
    lse0 = vary(jnp.full(q.shape[:2] + (1,), -jnp.inf, jnp.float32))

    def body(i, carry):
        out, lse, k_cur, v_cur = carry[:4]
        km_cur = carry[4] if has_km else None
        src = jnp.mod(my - i, n)
        offs = jnp.stack([my * t, src * t]).astype(jnp.int32)
        o_b, lse_b = flash_block_fwd(q, k_cur, v_cur, km_cur, offs,
                                     causal, groups=groups)
        out, lse = _merge_blocks(out, lse, o_b, lse_b)
        pp = lambda x: lax.ppermute(x, axis_name, _ring_perm(n))
        return (out, lse, pp(k_cur), pp(v_cur)) + (
            (pp(km_cur),) if has_km else ())

    init = (out0, lse0, k, v) + ((km,) if has_km else ())
    res = lax.fori_loop(0, n, body, init)
    return res[0].astype(q.dtype), res[1]


def _ring_bwd_impl(q, k, v, km, out, lse, g, axis_name, causal,
                   groups):
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    t = q.shape[1]
    has_km = km is not None
    _vary = _vary_like(getattr(jax.typeof(q), "vma", frozenset()),
                       axis_name)
    zero = lambda x: _vary(jnp.zeros(x.shape, jnp.float32))

    def body(i, carry):
        dq, dk_acc, dv_acc, k_cur, v_cur = carry[:5]
        km_cur = carry[5] if has_km else None
        src = jnp.mod(my - i, n)
        offs = jnp.stack([my * t, src * t]).astype(jnp.int32)
        # dk_b/dv_b come back already reduced to the kv head count
        dq_b, dk_b, dv_b = flash_block_bwd(
            q, k_cur, v_cur, out, lse, g, km_cur, offs, causal,
            groups=groups)
        dq = dq + dq_b.astype(jnp.float32)
        dk_acc = dk_acc + dk_b.astype(jnp.float32)
        dv_acc = dv_acc + dv_b.astype(jnp.float32)
        # dk/dv accumulators travel with their kv block; after n
        # rotations each block (and its now-complete gradient) is home
        pp = lambda x: lax.ppermute(x, axis_name, _ring_perm(n))
        return (dq, pp(dk_acc), pp(dv_acc), pp(k_cur), pp(v_cur)) + (
            (pp(km_cur),) if has_km else ())

    init = (zero(q), zero(k), zero(v), k, v) + (
        (km,) if has_km else ())
    res = lax.fori_loop(0, n, body, init)
    dq, dk, dv = res[0], res[1], res[2]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ring_attn(q, k, v, km, axis_name, causal, groups=1):
    out, _ = _ring_fwd_impl(q, k, v, km, axis_name, causal, groups)
    return out


def _ring_attn_fwd(q, k, v, km, axis_name, causal, groups):
    out, lse = _ring_fwd_impl(q, k, v, km, axis_name, causal, groups)
    return out, (q, k, v, km, out, lse)


def _ring_attn_bwd(axis_name, causal, groups, res, g):
    q, k, v, km, out, lse = res
    dq, dk, dv = _ring_bwd_impl(q, k, v, km, out, lse, g, axis_name,
                                causal, groups)
    return dq, dk, dv, None if km is None else jnp.zeros_like(km)


_ring_attn.defvjp(_ring_attn_fwd, _ring_attn_bwd)


def _fold_dispatch(attn_fn, q, k, v, mask, mesh, axis_name,
                   batch_axis=None, head_axis=None):
    """Shared [B,T,H,D] → ring dispatch: GQA head-count check, head
    folding to [B·H, T_loc, D], key-mask folding to [B·Hkv, T_loc]
    (None stays None — no mask tensor enters the ring), shard_map over
    ``axis_name``. ``attn_fn(qf, kf, vf, km, groups)`` runs on the
    per-device folded blocks.

    ``batch_axis`` / ``head_axis``: mesh axes the batch and head dims
    are ALREADY sharded over (composed DP×SP×TP training — the whole
    step runs under one jit over a multi-axis mesh). Naming them in
    the shard_map specs lets the data/tensor shardings ride straight
    through the ring instead of being all-gathered at its boundary;
    the ring's collectives still touch only ``axis_name``."""
    def local(q, k, v, kmask):
        b, t, h, d = q.shape
        h_kv = k.shape[2]
        if h % h_kv:
            raise ValueError(f"q heads ({h}) not divisible by kv "
                             f"heads ({h_kv})")
        fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
            b * x.shape[2], t, d)
        km = (None if kmask is None
              else jnp.repeat(kmask.astype(jnp.float32), h_kv, axis=0))
        o = attn_fn(fold(q), fold(k), fold(v), km, h // h_kv)
        return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    spec = P(batch_axis, axis_name, head_axis, None)
    if mask is None:
        fn = shard_map(lambda q, k, v: local(q, k, v, None), mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)
    fn = shard_map(local, mesh=mesh,
                   in_specs=(spec, spec, spec,
                             P(batch_axis, axis_name)),
                   out_specs=spec)
    return fn(q, k, v, mask)


def ring_self_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                        mask: Optional[jax.Array] = None,
                        causal: bool = False, batch_axis=None,
                        head_axis=None):
    """Distributed attention: inputs [B, T, H, D] sharded on T over
    ``axis_name``; returns [B, T, H, D] with identical sharding.

    ``mask``: [B, T] key mask, sharded the same way. ``causal``: mask
    above the global diagonal (works across ring steps via per-block
    position offsets — the long-context causal-LM training path).
    Grouped-query attention: ``k``/``v`` may carry FEWER heads than
    ``q`` (H divisible by Hkv) — only the small kv rotates over ICI,
    expanded to the query heads at each flash call.
    ``batch_axis``/``head_axis``: mesh axes B and H are already
    sharded over (composed DP×SP×TP — see ``_fold_dispatch``).
    """
    return _fold_dispatch(
        lambda qf, kf, vf, km, groups: _ring_attn(
            qf, kf, vf, km, axis_name, causal, groups),
        q, k, v, mask, mesh, axis_name, batch_axis, head_axis)


# Ulysses all-to-all SP lives in parallel/ulysses.py; this alias
# preserves the original import location.
from deeplearning4j_tpu.parallel.ulysses import \
    ulysses_self_attention as ulysses_attention  # noqa: E402


# ---------------------------------------------------------------------------
# zigzag (load-balanced) causal ring attention
# ---------------------------------------------------------------------------
#
# Plain causal ring attention is imbalanced: ring index m has m+1 live
# KV blocks of n, so the last device does n× the work of the first and
# the ring's wall-clock is set by the worst device. The zigzag layout
# (Megatron-style context parallelism) gives every device TWO
# half-chunks — global chunk m and chunk 2n−1−m — so each device owns
# one early (cheap) and one late (expensive) piece of the causal
# triangle and every device computes exactly 2n+1 live half-chunk pairs
# per full ring: perfectly balanced, same O(T/N) memory, same ppermute
# volume.

def zigzag_order(n: int):
    """Global chunk order of the zigzag layout: device m holds chunks
    (m, 2n−1−m) of 2n equal chunks."""
    order = []
    for m in range(n):
        order += [m, 2 * n - 1 - m]
    return order


def zigzag_permute(x, n: int, axis: int = 1):
    """Reorder a gathered [..., T, ...] array into zigzag layout (call
    before sharding the sequence axis over the mesh)."""
    t = x.shape[axis]
    c = t // (2 * n)
    if t % (2 * n):
        raise ValueError(f"T={t} not divisible by 2·n_devices={2 * n}")
    idx = jnp.concatenate([jnp.arange(j * c, (j + 1) * c)
                           for j in zigzag_order(n)])
    return jnp.take(x, idx, axis=axis)


def zigzag_unpermute(x, n: int, axis: int = 1):
    """Inverse of :func:`zigzag_permute`."""
    t = x.shape[axis]
    c = t // (2 * n)
    idx = jnp.concatenate([jnp.arange(j * c, (j + 1) * c)
                           for j in zigzag_order(n)])
    inv = jnp.zeros_like(idx).at[idx].set(jnp.arange(t))
    return jnp.take(x, inv, axis=axis)


def _zz_merge_half(out, lse, o_b, lse_b, qi, c):
    sl = slice(qi * c, (qi + 1) * c)
    o_new, l_new = _merge_blocks(out[:, sl], lse[:, sl], o_b, lse_b)
    return out.at[:, sl].set(o_new), lse.at[:, sl].set(l_new)


def _zz_fwd_impl(q, k, v, km, axis_name, groups):
    """q: [B·H, 2c, D]; k,v: [B·Hkv, 2c, D], km: [B·Hkv, 2c] or None,
    all in zigzag layout (GQA: only the small kv — and its mask —
    rotates; km=None rotates nothing extra). Causal only."""
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    c = q.shape[1] // 2
    has_km = km is not None
    vary = _vary_like(getattr(jax.typeof(q), "vma", frozenset()),
                      axis_name)
    out0 = vary(jnp.zeros(q.shape, jnp.float32))
    lse0 = vary(jnp.full(q.shape[:2] + (1,), -jnp.inf, jnp.float32))
    q_ids = (my, 2 * n - 1 - my)
    qh = (q[:, :c], q[:, c:])

    def body(i, carry):
        out, lse, k_cur, v_cur = carry[:4]
        km_cur = carry[4] if has_km else None
        src = jnp.mod(my - i, n)
        k_ids = (src, 2 * n - 1 - src)
        for qi in (0, 1):
            for ki in (0, 1):
                ks = slice(ki * c, (ki + 1) * c)
                offs = jnp.stack([q_ids[qi] * c,
                                  k_ids[ki] * c]).astype(jnp.int32)
                o_b, lse_b = flash_block_fwd(
                    qh[qi], k_cur[:, ks], v_cur[:, ks],
                    None if km_cur is None else km_cur[:, ks],
                    offs, True, groups=groups)
                out, lse = _zz_merge_half(out, lse, o_b, lse_b, qi, c)
        pp = lambda x: lax.ppermute(x, axis_name, _ring_perm(n))
        return (out, lse, pp(k_cur), pp(v_cur)) + (
            (pp(km_cur),) if has_km else ())

    init = (out0, lse0, k, v) + ((km,) if has_km else ())
    res = lax.fori_loop(0, n, body, init)
    return res[0].astype(q.dtype), res[1]


def _zz_bwd_impl(q, k, v, km, out, lse, g, axis_name, groups):
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    c = q.shape[1] // 2
    has_km = km is not None
    _vary = _vary_like(getattr(jax.typeof(q), "vma", frozenset()),
                       axis_name)
    zero = lambda x: _vary(jnp.zeros(x.shape, jnp.float32))
    q_ids = (my, 2 * n - 1 - my)
    qh = (q[:, :c], q[:, c:])
    outh = (out[:, :c], out[:, c:])
    lseh = (lse[:, :c], lse[:, c:])
    gh = (g[:, :c], g[:, c:])

    def body(i, carry):
        dq, dk_acc, dv_acc, k_cur, v_cur = carry[:5]
        km_cur = carry[5] if has_km else None
        src = jnp.mod(my - i, n)
        k_ids = (src, 2 * n - 1 - src)
        for qi in (0, 1):
            for ki in (0, 1):
                ks = slice(ki * c, (ki + 1) * c)
                offs = jnp.stack([q_ids[qi] * c,
                                  k_ids[ki] * c]).astype(jnp.int32)
                dq_b, dk_b, dv_b = flash_block_bwd(
                    qh[qi], k_cur[:, ks], v_cur[:, ks], outh[qi],
                    lseh[qi], gh[qi],
                    None if km_cur is None else km_cur[:, ks],
                    offs, True, groups=groups)
                qs = slice(qi * c, (qi + 1) * c)
                dq = dq.at[:, qs].add(dq_b.astype(jnp.float32))
                dk_acc = dk_acc.at[:, ks].add(dk_b.astype(jnp.float32))
                dv_acc = dv_acc.at[:, ks].add(dv_b.astype(jnp.float32))
        pp = lambda x: lax.ppermute(x, axis_name, _ring_perm(n))
        return (dq, pp(dk_acc), pp(dv_acc), pp(k_cur), pp(v_cur)) + (
            (pp(km_cur),) if has_km else ())

    init = (zero(q), zero(k), zero(v), k, v) + (
        (km,) if has_km else ())
    res = lax.fori_loop(0, n, body, init)
    dq, dk, dv = res[0], res[1], res[2]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _zz_ring_attn(q, k, v, km, axis_name, groups=1):
    out, _ = _zz_fwd_impl(q, k, v, km, axis_name, groups)
    return out


def _zz_ring_attn_fwd(q, k, v, km, axis_name, groups):
    out, lse = _zz_fwd_impl(q, k, v, km, axis_name, groups)
    return out, (q, k, v, km, out, lse)


def _zz_ring_attn_bwd(axis_name, groups, res, g):
    q, k, v, km, out, lse = res
    dq, dk, dv = _zz_bwd_impl(q, k, v, km, out, lse, g, axis_name,
                              groups)
    return dq, dk, dv, None if km is None else jnp.zeros_like(km)


_zz_ring_attn.defvjp(_zz_ring_attn_fwd, _zz_ring_attn_bwd)


def zigzag_ring_self_attention(q, k, v, mesh: Mesh,
                               axis_name: str = "seq",
                               mask: Optional[jax.Array] = None,
                               batch_axis=None, head_axis=None):
    """Load-balanced CAUSAL ring attention. Inputs [B, T, H, D] in
    ZIGZAG layout on the T axis (see :func:`zigzag_permute`), sharded
    over ``axis_name``; returns the same layout/sharding.

    Every device computes the same number of live half-chunk pairs per
    ring, so the causal triangle no longer serialises on the
    last-ranked device (plain ``ring_self_attention`` with
    ``causal=True`` is correct but its critical path is the device
    holding the final blocks). GQA: k/v may carry fewer heads than q.

    ``mask``: [B, T] key mask IN ZIGZAG LAYOUT (apply
    :func:`zigzag_permute` to the sequence-order mask alongside
    q/k/v), sharded the same way — packed-document / padded causal
    batches keep the balanced schedule. Masked key positions
    contribute nothing; rows whose query position is masked produce
    unspecified output (mask them downstream, as the dense path does).
    """
    return _fold_dispatch(
        lambda qf, kf, vf, km, groups: _zz_ring_attn(
            qf, kf, vf, km, axis_name, groups),
        q, k, v, mask, mesh, axis_name, batch_axis, head_axis)

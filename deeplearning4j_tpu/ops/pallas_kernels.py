"""Pallas (Mosaic) TPU kernels.

The reference accelerates its hot ops with hand-written CUDA/cuDNN
platform helpers dispatched before the generic implementation
(`include/ops/declarable/platform/cudnn/*.cu`, SURVEY §2.1). The
TPU-native analog: XLA already fuses almost everything; the few ops
that benefit from a hand-written kernel are implemented here with
Pallas and dispatched the same way — fast path when available,
generic jnp fallback otherwise.

Kernels:
- ``flash_attention`` — blockwise online-softmax attention
  (never materialises the [T,T] score matrix; VMEM-resident
  accumulators; MXU matmuls per block). Supports per-example key
  masks and dynamic global position offsets (for ring composition).
  Used by ``scaled_dot_attention`` for long sequences on TPU —
  including padded/masked batches — and composed per-KV-block by
  ``parallel.ring_attention`` over ICI (``flash_block_fwd`` /
  ``flash_block_bwd`` below are the composition surface: the ring
  carries (out, lse) accumulators between Pallas calls and merges
  them with exact log-sum-exp combination). A causal forward that
  keeps no ``lse`` (a serving bucket's prefill, ``generate()``'s, an
  evaluation) runs ``_prefill_kernel``: K and V of a kv head whole in
  VMEM, the loop over key blocks inside the kernel and bounded by the
  diagonal, the window and the prompt's length.
- ``paged_decode_attention`` — the serving decode step's attention
  over the paged KV pool, read in place: per slot a loop over the
  slot's pages bounded by its length, whole pages copied HBM -> VMEM
  by the kernel's own double-buffered DMAs (one pipeline over all
  slots of a call), float32 online softmax.
  ``_reference_paged_attention`` is its fallback and the attention of
  the multi-row paged programs.
- ``latent_decode_attention`` — the same page walk over the paged
  LATENT pool of a latent-attention model: a page is the ``[block,
  kv_rank + rope]`` matrix every absorbed query head reads at once,
  and the walk is one pipeline over all slots of a call.
- ``retention_decode`` / ``ssm_decode`` — one decode position of a
  power-retention block or a Mamba-2 layer for every live slot, the
  float32 recurrent state updated in place in the paged state pool:
  no grid, the call's live items walked in groups, the groups' reads
  and write-backs taking turns at the memory.
- ``threshold_encode`` / ``threshold_decode`` — fused gradient
  threshold compression (reference libnd4j ops ``encode_threshold`` /
  ``decode_threshold``): one VMEM pass computes the ternary
  quantisation, packs 16 two-bit codes per int32 word (16× smaller
  than f32), and emits the residual.

On CPU the kernels run in Pallas interpret mode (tests), so the same
code path is exercised everywhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret() -> bool:
    return jax.default_backend() not in ("tpu",)


def _vma(*xs) -> frozenset:
    """Union of the inputs' varying-manual-axes. Outside ``shard_map``
    this is empty; inside, ``pallas_call`` out_shapes must declare it
    (check_vma) — outputs vary over every axis an input varies over."""
    out: frozenset = frozenset()
    for x in xs:
        if x is not None:
            out = out | jax.typeof(x).vma
    return out


def _sds(shape, dtype, vma: frozenset):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _align_vma(x, vma: frozenset):
    """Broadcast a replicated operand onto varying manual axes so every
    kernel operand carries the same vma (mixed vmas trip check_vma
    inside pallas interpret mode)."""
    missing = vma - jax.typeof(x).vma
    return lax.pcast(x, tuple(missing), to="varying") if missing else x


def _jnp_fallback(*xs) -> bool:
    """Pallas interpret mode (CPU) cannot run under shard_map manual
    axes (its internal index ops trip check_vma) — use the equivalent
    jnp path there. Real TPU lowering handles manual axes natively."""
    return _interpret() and bool(_vma(*xs))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
#
# All kernels take, in addition to q/k/v:
#  - km_ref: [1, 1, block_k] per-(batch·head) key validity mask block
#    (1 = attend, 0 = padded key; kernels read km_ref[0, 0]) — the
#    reference cuDNN fused-attention helper's mask operand analog;
#    blocks whose mask is all-zero are skipped entirely. The mask
#    rides as [BHkv, 1, Tk]: Mosaic requires a block's last two dims
#    be (8, 128)-divisible OR equal to the array dims, and the unit
#    sublane axis satisfies that at zero memory cost (a 2-D
#    [BHkv, Tk] operand with (1, block_k) blocks does NOT lower).
#  - off_ref: SMEM int32 [2] = (q_offset, k_offset) GLOBAL position
#    offsets used for causal masking. (0, 0) for single-device
#    attention; ring attention passes (my_idx·Tq, src_idx·Tk) so the
#    causal diagonal lands correctly on every ring step and blocks
#    fully above the diagonal are skipped without any work.


def _fold_scores(s, v_dtype, load_v, m, l, acc):
    """Fold one masked float32 score tile ``s [bq, bk]`` and its values
    (``load_v()``: the ``[bk, d]`` tile of ``v_dtype``, read where it
    is used) into the online softmax the scratch refs hold: ``m`` the
    running maximum and ``l`` the running sum (lane 0 of 128 each),
    ``acc [bq, d]`` the weighted values. Masked entries are ``-inf``.
    ``_flash_kernel``'s grid steps and ``_prefill_kernel``'s loop
    turns are this one recurrence."""
    m_prev = m[:, :1]
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    # exp(-inf - -inf) guard: rows with no live keys yet keep m=-inf
    p = jnp.exp(s - jnp.where(jnp.isinf(m_new), 0.0, m_new))
    alpha = jnp.exp(jnp.where(jnp.isinf(m_prev), -jnp.inf, m_prev)
                    - jnp.where(jnp.isinf(m_new), 0.0, m_new))
    alpha = jnp.where(jnp.isinf(m_prev), 0.0, alpha)

    l[:, :1] = l[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc[:] = acc[:] * alpha + jnp.dot(p.astype(v_dtype), load_v(),
                                      preferred_element_type=jnp.float32)
    m[:, :1] = m_new


def _flash_kernel(q_ref, k_ref, v_ref, km_ref, off_ref, o_ref, *rest,
                  scale: float, causal: bool, t_real: int,
                  block_q: int, block_k: int,
                  window: Optional[int] = None):
    # rest = (lse_ref?, acc, m, l): the lse output only exists on the
    # differentiated path (inference pays no extra HBM writes)
    lse_ref = rest[0] if len(rest) == 4 else None
    acc, m, l = rest[-3:]
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m[:] = jnp.full_like(m[:], -jnp.inf)
        l[:] = jnp.zeros_like(l[:])
        acc[:] = jnp.zeros_like(acc[:])

    # skip dead blocks entirely (the einsum path can't): kv blocks
    # fully past the real sequence, blocks whose key mask is all-zero,
    # and — causal — blocks fully above the (offset) diagonal
    i = pl.program_id(1)
    km = km_ref[0, 0]
    live = jnp.logical_and(j * block_k < t_real, jnp.any(km > 0))
    if causal:
        q_off, k_off = off_ref[0], off_ref[1]
        live = jnp.logical_and(
            live,
            k_off + j * block_k <= q_off + i * block_q + block_q - 1)
        if window is not None:
            # a sliding window: query t sees keys t - window < j <= t;
            # a block whose last key lies at or below the first row's
            # bound is out of every row's range
            live = jnp.logical_and(
                live, k_off + j * block_k + block_k - 1
                > q_off + i * block_q - window)

    @pl.when(live)
    def _():
        # operands stay in their storage dtype (bf16 in-model): the MXU
        # runs native bf16×bf16→f32; casting to f32 first would force
        # the multi-pass f32 matmul path at a fraction of peak. The
        # softmax scale folds into the q TILE ([bq, d] mul) instead of
        # the score tile ([bq, bk] mul — bk/d× more VPU work).
        qs = q_ref[0] * q_ref.dtype.type(scale)
        s = jnp.dot(qs, k_ref[0].T, preferred_element_type=jnp.float32)

        # mask padded kv positions (t_real is the unpadded length),
        # key-masked positions and (causal) above-diagonal entries by
        # folding -inf into s: exp(s - m) then yields exact zeros, so
        # no separate p-masking is needed. (A lax.cond that skips the
        # mask arithmetic on interior blocks was measured SLOWER on
        # v5e — the Mosaic branch costs more than the VPU ops saved.)
        kv_idx = j * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = jnp.logical_and(kv_idx < t_real,
                               jnp.broadcast_to(km[None, :] > 0,
                                                (block_q, block_k)))
        if causal:
            q_idx = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(
                mask, off_ref[1] + kv_idx <= off_ref[0] + q_idx)
            if window is not None:
                mask = jnp.logical_and(
                    mask, off_ref[1] + kv_idx
                    > off_ref[0] + q_idx - window)
        s = jnp.where(mask, s, -jnp.inf)

        _fold_scores(s, v_ref.dtype, lambda: v_ref[0], m, l, acc)

    @pl.when(j == nk - 1)
    def _():
        den = jnp.maximum(l[:, :1], 1e-30)
        o_ref[0] = (acc[:] / den).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp residual for the backward kernels
            # (FlashAttention-2: p = exp(s - lse) recomputed per
            # block); -inf for rows with no live keys
            lse_ref[0] = jnp.broadcast_to(m[:, :1] + jnp.log(den),
                                          lse_ref.shape[1:])


def _flash_blocks(tq_real: int, tk_real: int, d: int, block_q: int,
                  block_k: int):
    q128 = -(-tq_real // 128) * 128
    k128 = -(-tk_real // 128) * 128
    block_q = min(block_q, q128)              # don't block past the data
    block_k = min(block_k, k128)
    if not _interpret():
        # Mosaic: the km operand's LANE dim is block_k, which must be
        # a multiple of 128 (or span the whole padded array) — clamp
        # caller-tuned sub-128 block_k up on real hardware (interpret
        # mode keeps small blocks so CPU tests exercise multi-block
        # grids at small T)
        block_k = min(-(-block_k // 128) * 128, k128)
    tq = -(-tq_real // block_q) * block_q     # q and kv padded separately
    tk = -(-tk_real // block_k) * block_k     # (≤ one partial block each)
    dp = max(-(-d // 128) * 128, 128)         # lane-align head dim
    return block_q, block_k, tq, tk, dp


def _ones_km(x):
    return jnp.ones(x.shape[:2], jnp.float32)


def _zero_offs():
    return jnp.zeros((2,), jnp.int32)


def _expand_kv_rows(x, groups):
    """[B·Hkv, ...] → [B·H, ...] for the jnp fallback paths (rows are
    (batch, head)-major; query head h reads kv head h // groups)."""
    return x if (x is None or groups == 1) else \
        jnp.repeat(x, groups, axis=0)


def _reduce_kv_rows(dx, groups):
    """Transpose of :func:`_expand_kv_rows`: sum the per-query-head
    kv gradients onto their shared kv head."""
    if groups == 1:
        return dx
    bh = dx.shape[0]
    return jnp.sum(dx.reshape(bh // groups, groups, *dx.shape[1:]),
                   axis=1)


def _flash_fwd(q, k, v, km, offs, causal: bool, block_q: int,
               block_k: int, return_lse: bool = False,
               groups: int = 1, window: Optional[int] = None,
               q_off: int = 0):
    """q: [B·H, T, D] (heads folded); k,v: [B·H/groups, Tk, D] —
    grouped-query attention reads ONE kv block per head group straight
    from HBM via the BlockSpec index map (``b // groups``), never
    materialising the broadcast; km: [B·H/groups, Tk] key mask;
    offs: int32 [2] global (q, k) position offsets. Returns [BH, T, D]
    (and, for the vjp / ring composition, the per-row [BH, Tq, 1]
    logsumexp). ``window`` (causal only; ``q_off`` then the STATIC
    query offset ``offs`` holds, its key offset 0): query ``t`` sees
    keys ``t - window < j <= t``; a KV block out of every row's range
    is neither multiplied nor fetched (its block index is clamped
    into the range, and a block index that repeats is not copied
    again)."""
    if window is not None and not causal:
        raise ValueError("a sliding window is causal")
    if km is None:
        km = _ones_km(k)
    if offs is None:
        offs = _zero_offs()
    if _jnp_fallback(q, k, v):
        return _reference_scan(q, _expand_kv_rows(k, groups),
                               _expand_kv_rows(v, groups),
                               _expand_kv_rows(km, groups), offs,
                               causal, return_lse=return_lse,
                               window=window)
    bh, t, d = q.shape
    if k.shape[0] * groups != bh:
        raise ValueError(f"kv rows ({k.shape[0]}) × groups ({groups}) "
                         f"!= q rows ({bh})")
    tk_real = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    block_q, block_k, tq, tk, dp = _flash_blocks(t, tk_real, d,
                                                 block_q, block_k)

    def pad(x, tpad):
        return jnp.pad(x, ((0, 0), (0, tpad - x.shape[1]),
                           (0, dp - d)))

    vma = _vma(q, k, v, km, offs)
    qp = _align_vma(pad(q, tq), vma)
    kp = _align_vma(pad(k, tk), vma)
    vp = _align_vma(pad(v, tk), vma)
    # km rides as [BHkv, 1, Tk]: Mosaic requires the block's last two
    # dims divisible by (8, 128) OR equal to the array dims — a unit
    # sublane axis satisfies that with zero memory overhead
    kmp = _align_vma(
        jnp.pad(km.astype(jnp.float32),
                ((0, 0), (0, tk - tk_real)))[:, None, :],
        vma)
    offs = _align_vma(offs.astype(jnp.int32), vma)
    nq, nk = tq // block_q, tk // block_k
    g = groups
    if window is None:
        kv_block = lambda i, j: j
    else:
        def kv_block(i, j):     # the blocks a q block's rows can see
            lo = jnp.maximum(q_off + i * block_q - window + 1, 0)
            hi = jnp.minimum(q_off + i * block_q + block_q - 1,
                             tk - 1)
            return jnp.clip(j, lo // block_k, hi // block_k)
    oshape = _sds((bh, tq, dp), q.dtype, vma)
    ospec = pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0))
    lshape = _sds((bh, tq, 128), jnp.float32, vma)
    lspec = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))
    res = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          t_real=tk_real, block_q=block_q,
                          block_k=block_k,
                          **({} if window is None
                             else {"window": window})),
        out_shape=(oshape, lshape) if return_lse else oshape,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dp),
                         lambda b, i, j: (b // g, kv_block(i, j), 0)),
            pl.BlockSpec((1, block_k, dp),
                         lambda b, i, j: (b // g, kv_block(i, j), 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j: (b // g, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(ospec, lspec) if return_lse else ospec,
        scratch_shapes=[
            pltpu.VMEM((block_q, dp), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_interpret(),
    )(qp, kp, vp, kmp, offs)
    if return_lse:
        out, lse = res
        # keep one lane per row as the residual (128x smaller);
        # _flash_bwd re-pads and re-broadcasts before its kernels
        return out[:, :t, :d], lse[:, :t, :1]
    return res[:, :t, :d]


def _reference_scan(q, k, v, km=None, offs=None, causal: bool = False,
                    block: int = 512, return_lse: bool = False,
                    window: Optional[int] = None):
    """Differentiable O(T)-memory blockwise attention in plain jnp
    (lax.scan over kv blocks) — the backward path and CPU fallback.
    Same mask/offset semantics as the Pallas kernel."""
    bh, t, d = q.shape
    tk_real = k.shape[1]
    tp = -(-tk_real // block) * block
    kp = jnp.pad(k, ((0, 0), (0, tp - tk_real), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, tp - tk_real), (0, 0)))
    kmp = (jnp.ones((bh, tp), jnp.float32) if km is None else
           jnp.pad(km.astype(jnp.float32),
                   ((0, 0), (0, tp - tk_real))))
    q_off = 0 if offs is None else offs[0]
    k_off = 0 if offs is None else offs[1]
    scale = 1.0 / (d ** 0.5)
    q_idx = q_off + jnp.arange(t)[:, None]

    def step(carry, blk):
        m_prev, l_prev, acc = carry
        kb, vb, kmb, j0 = blk
        s = jnp.einsum("bqd,bkd->bqk", q, kb) * scale
        kv_idx = j0 + jnp.arange(block)[None, :]
        mask = jnp.logical_and(kv_idx < tk_real, kmb[:, None, :] > 0)
        if causal:
            mask = jnp.logical_and(mask, k_off + kv_idx <= q_idx)
        if window is not None:
            mask = jnp.logical_and(mask,
                                   k_off + kv_idx > q_idx - window)
        s = jnp.where(mask, s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - safe), 0.0)
        alpha = jnp.where(jnp.isinf(m_prev), 0.0,
                          jnp.exp(m_prev - safe))
        l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bqk,bkd->bqd", p, vb)
        return (m_new, l_new, acc), None

    nb = tp // block
    kb = kp.reshape(bh, nb, block, d).swapaxes(0, 1)
    vb = vp.reshape(bh, nb, block, d).swapaxes(0, 1)
    kmb = kmp.reshape(bh, nb, block).swapaxes(0, 1)
    j0s = jnp.arange(nb) * block
    # under shard_map the carry must share the operands' varying axes
    vma = _vma(q, k, v, km, offs)
    init = tuple(_align_vma(x, vma) for x in (
        jnp.full((bh, t, 1), -jnp.inf),
        jnp.zeros((bh, t, 1)), jnp.zeros((bh, t, d))))
    (m, l, acc), _ = lax.scan(step, init, (kb, vb, kmb, j0s))
    den = jnp.maximum(l, 1e-30)
    out = (acc / den).astype(q.dtype)
    if return_lse:
        return out, (m + jnp.log(den)).astype(jnp.float32)
    return out


def _flash_bwd_masks(i, j, q_off, k_off, km, tq_real, tk_real, block_q,
                     block_k, causal):
    """(q,kv) validity mask for one [block_q, block_k] tile."""
    q_idx = i * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kv_idx = j * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.logical_and(q_idx < tq_real, kv_idx < tk_real)
    mask = jnp.logical_and(mask, jnp.broadcast_to(
        km[None, :] > 0, (block_q, block_k)))
    if causal:
        mask = jnp.logical_and(mask, k_off + kv_idx <= q_off + q_idx)
    return mask


def _flash_bwd_p_ds(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, i, j,
                    q_off, k_off, km, tq_real, tk_real, block_q,
                    block_k, causal, scale):
    """Recompute the probability tile and dS for the backward pass
    (FlashAttention-2 eq. dS = P ∘ (dP − Δ), Δ = rowsum(dO ∘ O)).
    Matmul operands stay in storage dtype (native bf16 MXU mode);
    softmax math and accumulation are f32. The softmax scale is
    folded into the q tile for s (and left OUT of dS — callers scale
    dq/dk once at write-out, saving a [bq, bk] multiply per pair);
    the mask folds into s as -inf so exp(s - lse) zeros masked
    entries with no separate p-masking pass. Returned q/k/do are the
    storage-dtype tiles; p/ds are f32 (cast to the operand dtype at
    their consuming matmuls, FA2-style)."""
    q, k, do = q_ref[0], k_ref[0], do_ref[0]
    qs = q * q_ref.dtype.type(scale)
    s = jnp.dot(qs, k.T, preferred_element_type=jnp.float32)
    mask = _flash_bwd_masks(i, j, q_off, k_off, km, tq_real,
                            tk_real, block_q, block_k, causal)
    s = jnp.where(mask, s, -jnp.inf)
    lse = lse_ref[0][:, :1]
    lse = jnp.where(jnp.isfinite(lse), lse, 0.0)
    p = jnp.exp(s - lse)
    delta = jnp.sum(do.astype(jnp.float32)
                    * o_ref[0].astype(jnp.float32), axis=-1,
                    keepdims=True)
    dp = jnp.dot(do, v_ref[0].T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return q, k, do, p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         km_ref, off_ref, dq_ref, acc, *, scale, causal,
                         tq_real, tk_real, block_q, block_k):
    i, j = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        acc[:] = jnp.zeros_like(acc[:])

    km = km_ref[0, 0]
    q_off, k_off = off_ref[0], off_ref[1]
    live = jnp.logical_and(j * block_k < tk_real, jnp.any(km > 0))
    if causal:
        live = jnp.logical_and(
            live,
            k_off + j * block_k <= q_off + i * block_q + block_q - 1)

    @pl.when(live)
    def _():
        _, k, _, _, ds = _flash_bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, i, j, q_off,
            k_off, km, tq_real, tk_real, block_q, block_k, causal,
            scale)
        acc[:] += jnp.dot(ds.astype(k.dtype), k,
                          preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _():
        # dS carries no scale — applied once here ([bq, d] mul)
        dq_ref[0] = (acc[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          km_ref, off_ref, dk_ref, dv_ref, acck, accv,
                          *, scale, causal, tq_real, tk_real, block_q,
                          block_k):
    j, i = pl.program_id(1), pl.program_id(2)   # kv outer, q inner
    nq = pl.num_programs(2)

    @pl.when(i == 0)
    def _():
        acck[:] = jnp.zeros_like(acck[:])
        accv[:] = jnp.zeros_like(accv[:])

    km = km_ref[0, 0]
    q_off, k_off = off_ref[0], off_ref[1]
    live = jnp.logical_and(i * block_q < tq_real, jnp.any(km > 0))
    if causal:
        live = jnp.logical_and(
            live,
            q_off + i * block_q + block_q - 1 >= k_off + j * block_k)

    @pl.when(live)
    def _():
        q, _, do, p, ds = _flash_bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, i, j, q_off,
            k_off, km, tq_real, tk_real, block_q, block_k, causal,
            scale)
        accv[:] += jnp.dot(p.astype(do.dtype).T, do,
                          preferred_element_type=jnp.float32)
        acck[:] += jnp.dot(ds.astype(q.dtype).T, q,
                          preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        dk_ref[0] = (acck[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = accv[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                            km_ref, off_ref, dq_ref, dk_ref, dv_ref,
                            dq_acc, acck, accv, *, scale, causal,
                            tq_real, tk_real, block_q, block_k):
    """Single-pass FA2 backward: grid (bh, kv, q). Each (kv, q) block
    pair recomputes s/p/dS ONCE and feeds all three gradient matmuls
    (the split kernels recompute the pair twice — ~7 matmul-class ops
    per pair vs 5 here, and they stream q/k/v/do from HBM twice).
    dk/dv accumulate in per-kv-block VMEM scratch, written when the
    inner q sweep ends; dq accumulates in a full-length f32 VMEM
    scratch (contributions to q block i arrive once per OUTER kv step,
    so a per-block buffer can't persist) and streams the running
    partial to the output each step — the final kv iteration's flush
    is the converged value."""
    j, i = pl.program_id(1), pl.program_id(2)   # kv outer, q inner
    nq = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        # first visit of q block i this row: zero its dq scratch slice
        dq_acc[pl.ds(i * block_q, block_q)] = jnp.zeros(
            (block_q, dq_acc.shape[1]), jnp.float32)

    @pl.when(i == 0)
    def _():
        acck[:] = jnp.zeros_like(acck[:])
        accv[:] = jnp.zeros_like(accv[:])

    km = km_ref[0, 0]
    q_off, k_off = off_ref[0], off_ref[1]
    live = jnp.logical_and(
        jnp.logical_and(i * block_q < tq_real, j * block_k < tk_real),
        jnp.any(km > 0))
    if causal:
        live = jnp.logical_and(
            live,
            q_off + i * block_q + block_q - 1 >= k_off + j * block_k)

    @pl.when(live)
    def _():
        q, k, do, p, ds = _flash_bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, i, j, q_off,
            k_off, km, tq_real, tk_real, block_q, block_k, causal,
            scale)
        accv[:] += jnp.dot(p.astype(do.dtype).T, do,
                           preferred_element_type=jnp.float32)
        acck[:] += jnp.dot(ds.astype(q.dtype).T, q,
                           preferred_element_type=jnp.float32)
        dq_acc[pl.ds(i * block_q, block_q)] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _():
        # dS carries no scale — applied once at write-out
        dk_ref[0] = (acck[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = accv[:].astype(dv_ref.dtype)

    dq_ref[0] = (dq_acc[pl.ds(i * block_q, block_q)]
                 * scale).astype(dq_ref.dtype)


# full-length dq scratch budget for the fused backward (f32 bytes).
# The kernel's total scoped VMEM is the dq scratch + dk/dv
# accumulators + double-buffered operand blocks (measured 17.1 MB at
# T=8192, bq=1024, bk=1024, dp=128), which exceeds Mosaic's 16 MB
# DEFAULT scoped-vmem limit — the fused call raises its
# vmem_limit_bytes to _FUSED_BWD_VMEM_LIMIT (physical VMEM on v5e is
# far larger). Past the scratch budget (T ≳ 24k at d≤128) fall back
# to the split kernels.
_FUSED_BWD_DQ_VMEM = 12 * 1024 * 1024
_FUSED_BWD_VMEM_LIMIT = 48 * 1024 * 1024


def _flash_bwd(q, k, v, out, lse, g, km, offs, causal, block_q,
               block_k, groups: int = 1):
    """Backward kernels. GQA (``groups`` > 1): kv operands stay at
    [B·Hkv] rows and are shared across each head group via the index
    map; dk/dv are produced per QUERY head (the accumulation grid runs
    per q head) and reduced onto the kv heads afterwards."""
    if _jnp_fallback(q, k, v, g):
        # shard_map manual axes on CPU: interpret-mode pallas can't run
        # there — exact jnp backward from the global lse instead
        dq, dk, dv = _reference_bwd_block(
            q, _expand_kv_rows(k, groups), _expand_kv_rows(v, groups),
            out, lse, g, _expand_kv_rows(km, groups), offs, causal)
        return (dq, _reduce_kv_rows(dk, groups),
                _reduce_kv_rows(dv, groups))
    if km is None:
        km = _ones_km(k)
    if offs is None:
        offs = _zero_offs()
    bh, t, d = q.shape
    if k.shape[0] * groups != bh:
        raise ValueError(f"kv rows ({k.shape[0]}) × groups ({groups}) "
                         f"!= q rows ({bh})")
    tk_real = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    block_q, block_k, tq, tk, dp = _flash_blocks(t, tk_real, d,
                                                 block_q, block_k)

    def pad(x, tpad):
        return jnp.pad(x, ((0, 0), (0, tpad - x.shape[1]),
                           (0, dp - d)))

    vma = _vma(q, k, v, g, km, offs)
    qp = _align_vma(pad(q, tq), vma)
    kp = _align_vma(pad(k, tk), vma)
    vp = _align_vma(pad(v, tk), vma)
    dop = _align_vma(pad(g, tq), vma)
    op = _align_vma(pad(out, tq), vma)
    kmp = _align_vma(
        jnp.pad(km.astype(jnp.float32),
                ((0, 0), (0, tk - tk_real)))[:, None, :],
        vma)
    offs = _align_vma(offs.astype(jnp.int32), vma)
    # residual is [BH, Tq, 1]; kernels read a full 128-lane block
    lsep = _align_vma(jnp.broadcast_to(
        jnp.pad(lse, ((0, 0), (0, tq - t), (0, 0))), (bh, tq, 128)),
        vma)
    nq, nk = tq // block_q, tk // block_k
    gg = groups
    kw = dict(scale=scale, causal=causal, tq_real=t, tk_real=tk_real,
              block_q=block_q, block_k=block_k)
    sspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    # grid (bh, j, i): kv-side blocks follow grid axis 1, q axis 2;
    # dk/dv land per QUERY head and are group-reduced below
    qspec2 = pl.BlockSpec((1, block_q, dp), lambda b, y, x: (b, x, 0))
    lspec2 = pl.BlockSpec((1, block_q, 128), lambda b, y, x: (b, x, 0))
    kspec2 = pl.BlockSpec((1, block_k, dp),
                          lambda b, y, x: (b // gg, y, 0))
    kmspec2 = pl.BlockSpec((1, 1, block_k),
                           lambda b, y, x: (b // gg, 0, y))
    ospec2 = pl.BlockSpec((1, block_k, dp), lambda b, y, x: (b, y, 0))
    if tq * dp * 4 <= _FUSED_BWD_DQ_VMEM:
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_fused_kernel, **kw),
            out_shape=(_sds((bh, tq, dp), q.dtype, vma),
                       _sds((bh, tk, dp), k.dtype, vma),
                       _sds((bh, tk, dp), v.dtype, vma)),
            grid=(bh, nk, nq),
            in_specs=[qspec2, kspec2, kspec2, qspec2, qspec2, lspec2,
                      kmspec2, sspec],
            out_specs=(qspec2, ospec2, ospec2),
            scratch_shapes=[pltpu.VMEM((tq, dp), jnp.float32),
                            pltpu.VMEM((block_k, dp), jnp.float32),
                            pltpu.VMEM((block_k, dp), jnp.float32)],
            compiler_params=None if _interpret() else
            pltpu.CompilerParams(
                vmem_limit_bytes=_FUSED_BWD_VMEM_LIMIT),
            interpret=_interpret(),
        )(qp, kp, vp, dop, op, lsep, kmp, offs)
        return (dq[:, :t, :d],
                _reduce_kv_rows(dk[:, :tk_real, :d], groups),
                _reduce_kv_rows(dv[:, :tk_real, :d], groups))
    # very long sequences: the full-length dq scratch would not fit in
    # VMEM — split dq / dkv passes with per-block accumulators
    qspec = pl.BlockSpec((1, block_q, dp), lambda b, x, y: (b, x, 0))
    lspec = pl.BlockSpec((1, block_q, 128), lambda b, x, y: (b, x, 0))
    kspec = pl.BlockSpec((1, block_k, dp),
                         lambda b, x, y: (b // gg, y, 0))
    kmspec = pl.BlockSpec((1, 1, block_k),
                          lambda b, x, y: (b // gg, 0, y))
    # grid (bh, i, j): q-side blocks follow grid axis 1, kv axis 2
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kw),
        out_shape=_sds((bh, tq, dp), q.dtype, vma),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, qspec, lspec, kmspec,
                  sspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32)],
        interpret=_interpret(),
    )(qp, kp, vp, dop, op, lsep, kmp, offs)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kw),
        out_shape=(_sds((bh, tk, dp), k.dtype, vma),
                   _sds((bh, tk, dp), v.dtype, vma)),
        grid=(bh, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, qspec2, lspec2,
                  kmspec2, sspec],
        out_specs=(ospec2, ospec2),
        scratch_shapes=[pltpu.VMEM((block_k, dp), jnp.float32),
                        pltpu.VMEM((block_k, dp), jnp.float32)],
        interpret=_interpret(),
    )(qp, kp, vp, dop, op, lsep, kmp, offs)
    return (dq[:, :t, :d],
            _reduce_kv_rows(dk[:, :tk_real, :d], groups),
            _reduce_kv_rows(dv[:, :tk_real, :d], groups))


def _reference_bwd_block(q, k, v, out, lse, g, km, offs, causal):
    """jnp backward for one (q-block, kv-block) pair given the global
    logsumexp — the interpret-mode/shard_map fallback of
    ``flash_block_bwd``. O(Tq·Tk) memory but only used on CPU tests."""
    bh, t, d = q.shape
    scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_idx = (0 if offs is None else offs[0]) + jnp.arange(t)[:, None]
    kv_idx = ((0 if offs is None else offs[1])
              + jnp.arange(k.shape[1])[None, :])
    mask = (jnp.ones(s.shape, bool) if km is None
            else jnp.broadcast_to(km[:, None, :] > 0, s.shape))
    if causal:
        mask = jnp.logical_and(mask, (kv_idx <= q_idx)[None])
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), -1, keepdims=True)
    dp = jnp.einsum("bqd,bkd->bqk", gf, v.astype(jnp.float32))
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bqk,bkd->bqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bqk,bqd->bkd", ds, q.astype(jnp.float32))
    dv = jnp.einsum("bqk,bqd->bkd", p, gf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# --- the causal inference forward (a bucket's prefill) -----------------------
#
# ``_flash_kernel``'s grid is ``(B H, nq, nk)`` and every step of it
# takes its turn: a step above the diagonal skips its body and is still
# a step (and, without a window, a fetch), a row past the prompt's end
# costs what a real one costs, and K and V are fetched again for every
# q block of every query head of a group. The forward that needs no
# ``lse`` (a bucket's prefill, the dense ``generate()`` prefill, an
# evaluation) has a path of its own: K and V of ONE kv head lie in VMEM
# whole, fetched once a kv head (their block index does not change over
# the group's heads and the q blocks), and a q block walks its key
# blocks in a loop INSIDE the kernel, bounded by the diagonal, the
# window and the batch row's live length (:func:`prefill_visits`). A
# key block out of range is neither a grid step nor a fetch. What a
# turn of that loop costs is mostly FIXED a q row (the fold's column
# arithmetic, the accumulator's read and write), so the key blocks are
# wide, and the one at the diagonal is walked as a half where a half
# is all that is left. PERF.md section 5 ("The flash prefill, taken
# apart") has the readings.

#: K and V of one kv head (one copy of each, padded) may take this much
#: of VMEM on the causal inference path; the pipeline holds them twice
_PREFILL_KV_BYTES = 8 * 1024 * 1024
_PREFILL_VMEM_BYTES = 48 * 1024 * 1024


class _Ints:
    """``minimum`` and ``maximum`` of plain integers, as
    :func:`prefill_visits` asks its ``xp`` for them."""
    minimum = staticmethod(min)
    maximum = staticmethod(max)


def prefill_visits(i, n, block_q: int, half: int,
                   window: Optional[int] = None, q_off: int = 0, xp=jnp):
    """The keys q block ``i`` walks when the first ``n`` query rows are
    live, in HALF key blocks of ``half`` keys: ``(lo, wide, narrow)``,
    the first half block that holds a visible (query, key) pair, then
    ``wide`` whole key blocks (two halves each, end to end from
    ``lo``) and ``narrow`` (0 or 1) half block after them, up to the
    last live row's diagonal. Row ``r`` stands at position ``q_off +
    r`` and sees keys ``pos - window < j <= pos``. Pure integer
    arithmetic on ``xp``'s minimum and maximum (``jnp`` in the kernel,
    :class:`_Ints` for the host's count): ONE function bounds the
    kernel's loops and counts what they multiply. The caller holds
    ``i * block_q < n``."""
    p0 = q_off + i * block_q                    # the first row's position
    last = q_off + xp.minimum(i * block_q + block_q, n) - 1
    lo = 0 * last if window is None else \
        xp.maximum(p0 - window + 1, 0) // half
    halves = last // half + 1 - lo
    return lo, halves // 2, halves % 2


def _prefill_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                    scale: float, block_q: int, block_k: int, half: int,
                    window: Optional[int], q_off: int, h_kv: int,
                    t_real: int):
    # len_ref [B] (scalar prefetch): live query rows a batch row;
    # q_ref/o_ref [1, block_q, dp]; k_ref/v_ref [1, Tk, dp]: ONE kv
    # head, whole; rest = (visits_ref?, acc, m, l): the count of the
    # loop's turns is an output only where a test asks for it
    acc, m, l = rest[-3:]
    i = pl.program_id(2)
    n = jnp.minimum(len_ref[pl.program_id(0) // h_kv], t_real)
    r0 = i * block_q
    if len(rest) == 4:
        rest[0][0, 0] = 0

    @pl.when(r0 >= n)
    def _():
        # no row of the block is live: no work, and ZEROS (a padded
        # row's K and V go on to the pages of the layers above, and
        # ``0 x NaN`` is ``NaN`` in a page walk's ``p v``)
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(r0 < n)
    def _():
        m[:] = jnp.full_like(m[:], -jnp.inf)
        l[:] = jnp.zeros_like(l[:])
        acc[:] = jnp.zeros_like(acc[:])
        # the softmax scale folds into the q tile, once a q block
        qs = q_ref[0] * q_ref.dtype.type(scale)
        lo, wide, narrow = prefill_visits(i, n, block_q, half, window,
                                          q_off)

        def fold(width: int, first):
            # ``width`` keys a turn from half block ``first`` on. The
            # mask's arithmetic runs in every turn: the chip reads no
            # difference without it (PERF.md section 5)
            def turn(j, carry):
                if len(rest) == 4:
                    rest[0][0, 0] += 1
                k0 = pl.multiple_of(first * half + j * width, half)
                s = jnp.dot(qs, k_ref[0, pl.ds(k0, width), :].T,
                            preferred_element_type=jnp.float32)
                kv_idx = k0 + lax.broadcasted_iota(
                    jnp.int32, (block_q, width), 1)
                q_idx = q_off + r0 + lax.broadcasted_iota(
                    jnp.int32, (block_q, width), 0)
                mask = kv_idx <= q_idx
                if window is not None:
                    mask = jnp.logical_and(mask, kv_idx > q_idx - window)
                _fold_scores(jnp.where(mask, s, -jnp.inf), v_ref.dtype,
                             lambda: v_ref[0, pl.ds(k0, width), :],
                             m, l, acc)
                return carry
            return turn

        if half == block_k:     # no half to walk: whole blocks alone
            lax.fori_loop(0, 2 * wide + narrow, fold(block_k, lo), 0)
        else:
            lax.fori_loop(0, wide, fold(block_k, lo), 0)
            lax.fori_loop(0, narrow, fold(half, lo + 2 * wide), 0)
        rows = r0 + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        o_ref[0] = jnp.where(
            rows < n, acc[:] / jnp.maximum(l[:, :1], 1e-30),
            0.0).astype(o_ref.dtype)


def _prefill_blocks(t: int, tk: int, d: int, window: Optional[int],
                    block_q: Optional[int], block_k: Optional[int]):
    """The causal inference path's blocks, ``_flash_blocks``' tuple and
    the HALF key block behind it: the caller's where given, else 512 x
    1024 (PERF.md section 5 has the sweep: a turn of the key loop has
    a fixed cost a q row, the fold's column arithmetic and the
    accumulator's read and write, that outweighs the pairs a wide
    block multiplies past an edge), a q block no taller than the
    window. A key block is walked whole where two halves are left up
    to the diagonal and as ONE half where one is: the turns of wide
    blocks, the pairs of narrow ones. A key block that does not halve
    into whole 128-lane tiles is its own half, on the chip and in
    interpret mode alike: the kernel then walks whole blocks alone,
    ``2 * wide + narrow`` of them."""
    if block_q is None:
        block_q = 512 if window is None else max(
            128, min(512, 1 << (int(window).bit_length() - 1)))
    if block_k is None:
        block_k = 1024
    block_q, block_k, tq, tk, dp = _flash_blocks(t, tk, d, block_q, block_k)
    half = block_k // 2 if block_k % 256 == 0 else block_k
    return block_q, block_k, tq, tk, dp, half


def _prefill_fits(t: int, tk: int, d: int, window: Optional[int],
                  itemsize: int, block_q=None, block_k=None) -> bool:
    """Whether K and V of ONE kv head, padded as the causal inference
    path pads them, lie within ``_PREFILL_KV_BYTES``."""
    *_, tkp, dp, _ = _prefill_blocks(t, tk, d, window, block_q, block_k)
    return 2 * tkp * dp * itemsize <= _PREFILL_KV_BYTES


def _prefill_qualifies(q, k, v, km, causal: bool, q_off: int,
                       window: Optional[int], block_q, block_k) -> bool:
    """Whether a forward without ``lse`` takes the causal inference
    path, by what the call shows: causal with static offsets that hide
    no query, no key mask, no manual axes, and K and V of a head
    within the VMEM budget."""
    return (causal and km is None and q_off >= 0
            and not _vma(q, k, v)
            and _prefill_fits(q.shape[1], k.shape[1], q.shape[2], window,
                              k.dtype.itemsize, block_q, block_k))


def _prefill_fwd(q, k, v, lengths, groups: int, window: Optional[int],
                 q_off: int, block_q, block_k, count_visits: bool = False):
    """The causal forward of ``_flash_fwd``'s operands by the kernel
    whose key loop runs inside it; ``lengths`` int32 ``[B]``: the live
    query rows a batch row (rows at and past it come back ZERO).
    ``count_visits``: also the turns of the key loop in each grid
    step, ``[B Hkv, groups, nq]`` (a test's eye on the loop)."""
    bh, t, d = q.shape
    bkv, tk_real = k.shape[:2]
    block_q, block_k, tq, tk, dp, half = _prefill_blocks(
        t, tk_real, d, window, block_q, block_k)

    def pad(x, tpad):
        return jnp.pad(x, ((0, 0), (0, tpad - x.shape[1]), (0, dp - d)))

    g, nq = groups, tq // block_q
    qspec = pl.BlockSpec((1, block_q, dp),
                         lambda b, gi, i, n: (b * g + gi, i, 0))
    kspec = pl.BlockSpec((1, tk, dp), lambda b, gi, i, n: (b, 0, 0))
    oshape = jax.ShapeDtypeStruct((bh, tq, dp), q.dtype)
    cshape = jax.ShapeDtypeStruct((bkv * g * nq, 1), jnp.int32)
    cspec = pl.BlockSpec((1, 1), lambda b, gi, i, n: ((b * g + gi) * nq + i,
                                                      0),
                         memory_space=pltpu.SMEM)
    res = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=1.0 / (d ** 0.5),
                          block_q=block_q, block_k=block_k, half=half,
                          window=window, q_off=q_off,
                          h_kv=bkv // lengths.shape[0], t_real=t),
        out_shape=(oshape, cshape) if count_visits else oshape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bkv, g, nq),
            in_specs=[qspec, kspec, kspec],
            out_specs=(qspec, cspec) if count_visits else qspec,
            scratch_shapes=[pltpu.VMEM((block_q, dp), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32)]),
        compiler_params=None if _interpret() else pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=_interpret(),
    )(lengths.astype(jnp.int32), pad(q, tq), pad(k, tk), pad(v, tk))
    if count_visits:
        return res[0][:, :t, :d], res[1].reshape(bkv, g, nq)
    return res[:, :t, :d]


def prefill_pairs(t: int, n: int, window: Optional[int], d: int,
                  itemsize: int):
    """What a causal forward of ``t`` rows, the first ``n`` live, costs
    ONE head in (query, key) pairs, ``(need, done)`` as plain
    integers: ``need`` what the live tokens see (``min(r + 1,
    window)`` keys a row), ``done`` the block areas the kernel
    multiplies, by :func:`prefill_visits`: the very bounds of the
    causal inference path's loops. (A call past that path's VMEM
    budget runs ``_flash_kernel``'s grid: its live steps are the same
    bounds at ITS blocks with every row of the bucket live.)"""
    n = min(int(n), t)
    seen = n if window is None else min(n, window)
    need = seen * (seen + 1) // 2 + (n - seen) * seen
    if _prefill_fits(t, t, d, window, itemsize):
        bq, *_, half = _prefill_blocks(t, t, d, window, None, None)
    else:
        bq, half, *_ = _flash_blocks(t, t, d,
                                     *_ring_block_defaults(None, None, t))
        n = t
    done = 0
    for i in range(-(-n // bq)):
        _, wide, narrow = prefill_visits(i, n, bq, half, window, xp=_Ints)
        done += (2 * wide + narrow) * bq * half
    return need, done


def _flash_infer(q, k, v, km, causal: bool, block_q, block_k,
                 groups: int = 1, window: Optional[int] = None,
                 q_off: int = 0, lengths=None):
    """A forward that keeps no ``lse``: the causal inference path where
    the call qualifies for it (:func:`_prefill_qualifies`), else
    ``_flash_kernel`` by ``_flash_fwd`` exactly as a training forward
    runs it. ``lengths`` as :func:`flash_attention`'s (``None``: every
    row is live)."""
    if _prefill_qualifies(q, k, v, km, causal, q_off, window, block_q,
                          block_k):
        if lengths is None:     # one length a kv row: all of them
            lengths = jnp.full((k.shape[0],), q.shape[1], jnp.int32)
        return _prefill_fwd(q, k, v, lengths, groups, window, q_off,
                            block_q, block_k)
    out = _flash_fwd(q, k, v, km, _static_offs(q_off), causal,
                     *_ring_block_defaults(block_q, block_k, k.shape[1]),
                     groups=groups, window=window, q_off=q_off)
    if lengths is None:
        return out
    live = jnp.repeat(lengths, q.shape[0] // lengths.shape[0])
    return jnp.where(jnp.arange(q.shape[1])[None, :, None]
                     < live[:, None, None], out, 0).astype(out.dtype)


# --- ring composition surface ------------------------------------------------
def _ring_block_defaults(block_q, block_k, tk):
    """The blocks of ``_flash_kernel`` and its backward, the ring's and
    ``flash_attention``'s training forward alike, from the v5e block
    sweep (tools/flash_crossover.py era, causal fwd+bwd): big q blocks
    amortise the backward's kv-side recompute, (1024, 512) wins up to
    4k keys (-28% vs the old 256/1024 at T=2048), (1024, 1024) at 8k
    keys (-16%); larger q blocks exceed VMEM at T=8k."""
    if block_q is None:
        block_q = 1024
    if block_k is None:
        block_k = 512 if tk <= 4096 else 1024
    return block_q, block_k


def flash_block_fwd(q, k, v, km=None, offs=None, causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    groups: int = 1):
    """One (local-Q × one-KV-block) flash forward returning
    ``(out, lse)`` — out is the softmax-normalised attention of q
    against ONLY this kv block, lse its per-row logsumexp. Two such
    partial results merge exactly via log-sum-exp combination
    (``ring_attention._merge_blocks``); the ring carries (out, lse)
    between Pallas calls. q: [B·H, T, D]; k,v: [B·H/groups, Tk, D]
    (GQA: the kernel shares one kv block per head group — no
    materialised broadcast); km: [B·H/groups, Tk]; offs: int32 [2]
    dynamic global (q, k) offsets for causal. Default blocks follow
    the measured v5e sweep — (1024, 512) up to 4k-key blocks (the
    usual ring regime; 1.44x vs the einsum pair at T/N=4096 in that
    sweep — not measured on today's code), block_k 1024 beyond."""
    from deeplearning4j_tpu.obs import devtime
    block_q, block_k = _ring_block_defaults(block_q, block_k,
                                            k.shape[1])
    with devtime.scope("ops.flash_block_fwd"):
        return _flash_fwd(q, k, v, km, offs, causal, block_q, block_k,
                          return_lse=True, groups=groups)


def flash_block_bwd(q, k, v, out, lse, g, km=None, offs=None,
                    causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, groups: int = 1):
    """Backward of one (q-block, kv-block) pair given the GLOBAL
    (all-blocks) out/lse — FlashAttention-2 style recompute. Returns
    (dq_contrib, dk, dv): dq_contrib sums over kv blocks; dk/dv are
    this block's totals (at the KV head count when ``groups`` > 1)
    once every q block has contributed. (_flash_bwd itself falls back
    to the jnp backward under shard_map-on-CPU.)"""
    from deeplearning4j_tpu.obs import devtime
    block_q, block_k = _ring_block_defaults(block_q, block_k,
                                            k.shape[1])
    with devtime.scope("ops.flash_block_bwd"):
        return _flash_bwd(q, k, v, out, lse, g, km, offs, causal,
                          block_q, block_k, groups=groups)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, km, causal, block_q, block_k, groups=1, q_off=0):
    # not differentiated: no ``lse`` is kept (blocks ``None``: each
    # path's own)
    return _flash_infer(q, k, v, km, causal, block_q, block_k,
                        groups=groups, q_off=q_off)


def _static_offs(q_off: int):
    return None if q_off == 0 else jnp.asarray([q_off, 0], jnp.int32)


def _flash_vjp_fwd(q, k, v, km, causal, block_q, block_k, groups,
                   q_off):
    out, lse = _flash_fwd(q, k, v, km, _static_offs(q_off), causal,
                          *_ring_block_defaults(block_q, block_k,
                                                k.shape[1]),
                          return_lse=True, groups=groups)
    return out, (q, k, v, km, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, groups, q_off, res, g):
    q, k, v, km, out, lse = res
    dkm = None if km is None else jnp.zeros_like(km)
    return _flash_bwd(q, k, v, out, lse, g, km, _static_offs(q_off),
                      causal, *_ring_block_defaults(block_q, block_k,
                                                    k.shape[1]),
                      groups=groups) + (dkm,)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    mask: Optional[jax.Array] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None,
                    lengths: Optional[jax.Array] = None):
    """Blockwise attention, [B, T, H, D] layout (head axis 2) like
    ``scaled_dot_attention``; ``mask``: optional [B, Tk] key mask.
    ``k``/``v`` may carry FEWER heads than ``q`` (grouped-query
    attention, H divisible by Hkv) — the kernels read the shared kv
    block per head group directly, no broadcast in HBM. Tq and Tk may
    differ (cross-attention / short-query-long-key); causal then masks
    against the END-ALIGNED diagonal (query row i attends keys
    ≤ i + Tk − Tq, matching the dense path's ``tril(..., Tk − Tq)``)
    — for valid rows: with Tq > Tk the leading Tq − Tk rows have NO
    live keys and the paths diverge there (kernel: zeros; einsum:
    uniform average), which is why ``_use_flash`` refuses causal
    Tq > Tk; mask such rows downstream if you call this directly.
    Differentiable: the backward is a pair of Pallas kernels (dQ;
    dK/dV) that recompute the probability tile per block from the
    saved logsumexp — FlashAttention-2 style, no [T,T] materialisation
    in either direction. ``window`` (causal only): query ``t`` sees
    the ``window`` keys ``t - window < j <= t``, its own included; KV
    blocks out of a q block's range are not read. The windowed forward
    has no backward yet (a window layer trains through the masked
    plain form). ``lengths`` (causal, forward only): int32 ``[B]``,
    the live query rows of each batch row (a padded prompt's tokens);
    rows at and past it cost nothing and come back ZERO. ``block_q``,
    ``block_k``: ``None`` leaves each path its own (the training sweep's
    for the forward that keeps ``lse`` and the backward, the causal
    inference path's for a forward that keeps none)."""
    b, t, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads ({h}) not divisible by kv heads "
                         f"({h_kv})")
    if lengths is not None and not causal:
        raise ValueError("lengths bound a causal forward")
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(
        b * x.shape[2], x.shape[1], -1)
    km = None
    if mask is not None:
        # per-example key mask → per-(batch·kv-head) rows
        km = jnp.repeat(mask.astype(jnp.float32), h_kv, axis=0)
    # devtime scope (ops/kernel_registry.py contract): the kernel's
    # own device time gets its own name in the gap report
    from deeplearning4j_tpu.obs import devtime
    with devtime.scope("ops.flash_attention"):
        q_off = k.shape[1] - t if causal else 0
        if window is not None or lengths is not None:
            o = _flash_infer(fold(q), fold(k), fold(v), km, causal,
                             block_q, block_k, groups=h // h_kv,
                             window=window, q_off=q_off, lengths=lengths)
        else:
            o = _flash(fold(q), fold(k), fold(v), km, causal, block_q,
                       block_k, h // h_kv, q_off)
    return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# paged decode attention (serving/scheduler.py's single-token step)
# ---------------------------------------------------------------------------
#
# The KV pool (serving/kv_pager.py) is ``[L, P, block, Hkv, 2D]``: one
# page of one layer is ONE contiguous run of ``block`` positions, each
# position the K (lanes ``0:D``) and V (lanes ``D:2D``) rows of every
# kv head. The kernel never sees a gathered context: per slot it walks
# the slot's page-table row up to the slot's length, copies whole
# pages HBM -> VMEM with its own DMAs, and folds each chunk into a
# float32 online softmax (the recurrence of ``_flash_kernel``). Pages
# past the length, trash pages and inactive slots are never touched.
#
# The walk is ONE software pipeline over all (slot, chunk) items of a
# call, in slot order (PR 40 gave it to the latent kernel below, PR 48
# to this one; the two share the helpers that issue, await and look
# ahead): while an item's rows are folded, the NEXT item's pages are
# in flight into the buffer's other half, be that the same slot's next
# chunk or the first chunk of the next live slot. The grid stays one
# step a slot (its output block is the slot's); the buffer, its
# semaphores and the copies in flight carry over the steps' edges, so
# only a call's first item is waited for with nothing to fold. A copy
# is issued by ONE page number into pool and buffer with the
# compiler's bounds checks off (the scheduler holds every page number
# under the pool's page count where it builds the feed), an item is
# awaited once a set bit of its page count, and a chunk is folded up
# to the quarter that holds its last fetched page.
#
# A page is read as the ``[block * Hkv, 2D]`` matrix it is: ALL query
# heads meet ALL of a chunk's rows in one [H, D] x [D, rows] matmul,
# and a row of another kv head is masked like a dead position. The
# MXU's time goes by the K and V rows pushed through it, which this
# does not change; what it spares is picking one head's rows out of
# every tile (a sublane gather per head, on packed bf16 rows).
#
# A head of 64 features (``packed``): K and V are the two HALVES of the
# one 128-lane row a position and kv head, and a lane slice at 64 is
# not a whole tile. So nothing is sliced: the query comes padded with
# zeros over the V lanes (``q . [K | V] = q . K`` exactly), the
# weighted sum takes whole rows, and the caller keeps the V half of
# the ``[H, 128]`` it gets back. The MXU multiplies twice the width;
# the bytes read, which bound the call, are the same.

#: rows of a chunk (positions x kv heads) folded per loop iteration:
#: one constant, read against the operands' shapes. PERF.md §5 ("The
#: KV walk, taken apart") has the sweep at the serving cells' shapes
_PAGED_CHUNK_ROWS = 4096


def _first_live(n_ref, j, n_slots):
    """The first live slot at or after ``j``; ``n_slots`` where there
    is none (a page walk's pipeline looks for the slot whose first
    chunk follows this slot's last)."""
    return lax.while_loop(
        lambda j: (j < n_slots)
        & (n_ref[jnp.minimum(j, n_slots - 1)] == 0),
        lambda j: j + 1, j)


def _issue_pages(pt_ref, pool_ref, buf, done, pool0, first, buf0, lo, hi):
    """Start the copies of pages ``lo .. hi - 1`` of one (slot, chunk)
    item: page ``j`` is entry ``first + j`` of the flat page table, a
    row of the pool ``[L * P, ...]`` past ``pool0``, and goes to row
    ``buf0 + j`` of the buffer ``[2 * chunk, ...]``. Pool and buffer
    are indexed by ONE page number each, the terms that do not change
    over an item summed by the caller: with the compiler's bounds
    checks off the scalar core issues a copy in 10 to 12 instruction
    bundles (33 to 41 with a layer and a half to multiply out a page,
    a ring's compare and select, and both addresses of every copy
    checked; PERF.md §5). The loop stays rolled."""
    def page(j, carry):
        pltpu.make_async_copy(pool_ref.at[pool0 + pt_ref[first + j]],
                              buf.at[buf0 + j], done).start()
        return carry

    lax.fori_loop(lo, hi, page, 0)


def _await_pages(pool_ref, buf, done, n, chunk):
    """Wait for the ``n`` page copies of an item: a copy adds its
    bytes to the half's semaphore and a wait takes its descriptor's
    bytes off, so ONE wait a set bit of ``n`` does, not one a page."""
    k = 1
    while k <= chunk:
        @pl.when(n & k != 0)
        def _(k=k):
            pltpu.make_async_copy(pool_ref.at[pl.ds(0, k)],
                                  buf.at[pl.ds(0, k)], done).wait()
        k *= 2


def _fold_sizes(chunk: int):
    """The static sizes (pages) a chunk is folded at: up to the quarter
    that holds its last fetched page."""
    return sorted({-(-chunk * k // 4) for k in range(1, 5)})


def _paged_decode_kernel(li_ref, pt_ref, n_ref, q_ref, pool_ref, o_ref,
                         buf, sem, turn, m, l, acc, *, scale: float,
                         block: int, n_kv: int, chunk: int,
                         max_pages: int, n_pool: int, d: int,
                         packed: bool = False,
                         window: Optional[int] = None):
    # li_ref [1], pt_ref [S*MP], n_ref [S]: scalar-prefetch operands in
    # SMEM; q_ref/o_ref [H, D] (this slot's block); pool_ref
    # [L*P, block*Hkv, 2D], left in HBM; buf [2*chunk, block*Hkv, 2D],
    # its halves end to end; turn [1] in SMEM: the half the next item
    # folded lies in. The walk is ``_latent_decode_kernel``'s: ONE
    # software pipeline over all (slot, chunk) items of a call
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    h = q_ref.shape[0]
    pool0 = li_ref[0] * n_pool

    def span(s):
        # slot s's walk: (its pages, the sequence's page it starts at,
        # that page's entry of the slot's row). A walk is held to the
        # row's length, whatever length it is handed: with the bounds
        # checks off, the page table is never read past a slot's row
        pages = (n_ref[s] + block - 1) // block
        if window is None:
            return jnp.minimum(pages, max_pages), 0, 0
        # the query, at n - 1, sees positions >= n - window: the walk
        # starts at the page that holds the first of them, and reads
        # the slot's row of the page table modulo its length (a row
        # shorter than the sequence is a RING: page p of the sequence
        # lies in entry p % max_pages)
        first = jnp.maximum(n_ref[s] - window, 0) // block
        return (jnp.minimum(pages - first, max_pages), first,
                first % max_pages)

    def issue(s, c, half):
        # the page copies of item (slot s, chunk c) into buf's half
        pages, _, entry = span(s)
        n = jnp.minimum(chunk, pages - c * chunk)
        done = sem.at[half]
        buf0 = half * chunk
        at = entry + c * chunk
        if window is None:
            _issue_pages(pt_ref, pool_ref, buf, done, pool0,
                         s * max_pages + at, buf0, 0, n)
            return
        # a walk has max_pages pages at most, so it wraps once at
        # most: an item is the run up to the row's end and the run
        # from its start, their bounds computed once (a compare and a
        # select a page cost the walk as much as the copy's issue)
        at = jnp.where(at >= max_pages, at - max_pages, at)
        till = jnp.minimum(n, max_pages - at)
        row = s * max_pages + at
        _issue_pages(pt_ref, pool_ref, buf, done, pool0, row, buf0, 0,
                     till)
        _issue_pages(pt_ref, pool_ref, buf, done, pool0,
                     row - max_pages, buf0, till, n)

    @pl.when(b == 0)
    def _():
        # a chunk's unfetched tail meets p == 0 in the p·V matmul, and
        # 0 · NaN is NaN: start from zeros, not from whatever bit
        # patterns VMEM holds (afterwards the tail is stale finite KV)
        buf[...] = jnp.zeros_like(buf)
        turn[0] = 0
        first = _first_live(n_ref, 0, n_slots)

        @pl.when(first < n_slots)
        def _():
            issue(first, 0, 0)

    m[...] = jnp.full_like(m, -jnp.inf)
    l[...] = jnp.zeros_like(l)
    acc[...] = jnp.zeros_like(acc)

    n_pos = n_ref[b]                  # live positions; 0 = inactive
    n_pages, first, _ = span(b)
    n_chunks = (n_pages + chunk - 1) // chunk
    # the slot whose first chunk follows this one's last; an inactive
    # slot walks nothing and looks for nothing
    after = _first_live(n_ref, jnp.where(n_pos > 0, b + 1, n_slots),
                        n_slots)
    half0 = turn[0]
    contract = (((1,), (1,)), ((), ()))

    def fold(kv, base):
        # kv [R, 2D]: the chunk's first R rows, the first of them at
        # position ``base``. Row r is kv head r % Hkv at the chunk's
        # position r // Hkv; query head i reads kv head i // (H // Hkv)
        rows = kv.shape[0]
        s = lax.dot_general(q_ref[...], kv if packed else kv[:, :d],
                            contract, preferred_element_type=jnp.float32)
        row = lax.broadcasted_iota(jnp.int32, (h, rows), 1)
        own = (lax.broadcasted_iota(jnp.int32, (h, rows), 0)
               // (h // n_kv) == row % n_kv)
        rel = row // n_kv
        live = jnp.logical_and(own, rel < n_pos - base)
        if window is not None:  # the head of the walk's first page
            live = jnp.logical_and(live, rel >= n_pos - window - base)
        # every chunk walked holds a live position of every kv head,
        # so each row's running maximum is finite from the first on
        s = jnp.where(live, s * scale, -jnp.inf)
        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l[...] = jnp.broadcast_to(
            l[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l.shape)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(kv.dtype), kv if packed else kv[:, d:],
            preferred_element_type=jnp.float32)
        m[...] = jnp.broadcast_to(m_new, m.shape)

    # the matmuls' shapes are static, so each size is a branch
    sizes = _fold_sizes(chunk)

    def body(c, carry):
        half = (half0 + c) % 2
        more = c + 1 < n_chunks
        s_next = jnp.where(more, b, after)

        @pl.when(s_next < n_slots)
        def _():
            issue(s_next, jnp.where(more, c + 1, 0), 1 - half)

        pages = jnp.minimum(chunk, n_pages - c * chunk)
        _await_pages(pool_ref, buf, sem.at[half], pages, chunk)
        base = (first + c * chunk) * block
        buf0 = pl.multiple_of(half * chunk, chunk)
        for lo, hi in zip([0] + sizes, sizes):
            @pl.when((lo < pages) & (pages <= hi))
            def _(hi=hi):
                fold(buf[pl.ds(buf0, hi)].reshape(hi * block * n_kv,
                                                  2 * d), base)
        return carry

    lax.fori_loop(0, n_chunks, body, 0)
    turn[0] = (half0 + n_chunks) % 2
    o_ref[...] = (acc[...] / jnp.maximum(l[:, :1], 1e-30)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("pages_per_chunk", "interpret",
                                    "window", "n_kv"))
def _paged_decode_call(q, pool, li, pt, n_live, pages_per_chunk,
                       interpret, window=None, n_kv=None):
    """ONE lowering for every layer of a step: the layer index is a
    scalar operand, so the unrolled blocks of ``serving.decode_step``
    share this jitted function's single ``func`` in the lowered
    module. A FOLDED pool ``[L, P, block * Hkv, 2D]`` (``n_kv``
    given) is read as it is stored."""
    s_, h, d = q.shape
    if pool.ndim == 4:
        n_l, n_p, rows, _ = pool.shape
        block = rows // n_kv
    else:
        n_l, n_p, block, n_kv, _ = pool.shape
    mp = pt.shape[1]
    chunk = pages_per_chunk
    # a 64-wide head: the query padded over the V lanes, whole rows
    # out, the V half kept (see above)
    packed = d % 128 != 0
    w = 2 * d if packed else d
    if packed:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, d)))
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=1.0 / (d ** 0.5),
                          block=block, n_kv=n_kv, chunk=chunk,
                          max_pages=mp, n_pool=n_p, d=d, packed=packed,
                          **({} if window is None
                             else {"window": window})),
        out_shape=jax.ShapeDtypeStruct((s_, h, w), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s_,),
            in_specs=[pl.BlockSpec((None, h, w), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, h, w),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2 * chunk, block * n_kv, 2 * d), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, w), jnp.float32),
            ]),
        # the buffer and the copies in flight carry from slot to slot:
        # the grid is a sequence, not a parallel map. The compiler's
        # check of every copy's two addresses is two thirds of the
        # scalar core's work a page: a buffer row is the loop's
        # counter, and a page number is the pager's own, held under
        # the pool's page count by the scheduler where it builds the
        # feed (``DecodeScheduler._check_feed``)
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name="paged_decode_attention",
    )(li.reshape(1).astype(jnp.int32), pt.reshape(-1).astype(jnp.int32),
      n_live.astype(jnp.int32), q,
      # the layers' pages end to end, a page as the matrix it is: with
      # whole sublane tiles a position (_use_paged_kernel) this is a
      # bitcast, not a copy
      pool.reshape(n_l * n_p, block * n_kv, 2 * d))
    return out[..., d:] if packed else out


def _reference_paged_attention(q, pool, li, pt, pos, window=None,
                               n_kv=None):
    """Attention of R query rows per slot over the paged pool in plain
    jnp: gather the slot's pages through its page-table row, put them
    back in position order, mask past each row's position. The
    registered fallback of :func:`paged_decode_attention` (the CPU
    runs it, the parity test compares against it) and, on every
    platform, the attention of the multi-row paged programs
    (speculative verify, suffix prefill).

    ``q`` [S, R, H, D]; ``pool`` ``(kv,)`` or the int8
    ``(codes, scales)`` of ``serving/kv_pager.py``; ``li`` the layer;
    ``pt`` [S, MP] i32; ``pos`` [S, R] i32: row r attends cache
    positions ``<= pos[s, r]``. Returns [S, R, H, D]. Mirrors
    ``nn/decoder_infer.py::DenseKV`` value-for-value (same scale
    factoring out of the einsums, same ``-1e9`` mask), which is what
    keeps paged greedy decode token-identical to dense ``generate()``:
    trash and stale positions sit past ``pos`` and get exact-zero
    softmax weight. ``window``: row r attends positions ``> pos[s, r]
    - window`` only, and, as in the kernel, the walk starts at the
    page of the first of them and reads ``pt``'s row modulo its
    length (a row shorter than the sequence is a ring); a folded pool
    ``[L, P, block * Hkv, 2D]`` comes with its ``n_kv``."""
    s_, r, h, d = q.shape
    if pool[0].ndim == 4:
        kv4 = pool[0]
        pool = (kv4.reshape(*kv4.shape[:2], kv4.shape[2] // n_kv, n_kv,
                            kv4.shape[3]),)
    base = None
    if window is not None:
        block, mp = pool[0].shape[2], pt.shape[1]
        first = jnp.maximum(pos.min(axis=1) + 1 - window, 0) // block
        walk = min(mp, -(-(window + r - 1) // block) + 1)
        pt = jnp.take_along_axis(
            pt, (first[:, None] + jnp.arange(walk)[None, :]) % mp,
            axis=1)
        base = (first * block)[:, None, None, None, None]
    n_kv = pool[0].shape[3]
    g = h // n_kv
    dt = q.dtype
    # [S, MP, block, Hkv, 2D] -> [S, Hkv, MP*block, 2D]
    ctx = pool[0][li, pt].transpose(0, 3, 1, 2, 4).reshape(
        s_, n_kv, -1, 2 * d)
    ck, cv = ctx[..., :d].astype(dt), ctx[..., d:].astype(dt)
    k_scale = v_scale = None
    if len(pool) == 2:
        # [S, MP, Hkv, 2, block] -> [S, Hkv, 2, MP*block]
        sc = pool[1][li, pt].transpose(0, 2, 3, 1, 4).reshape(
            s_, n_kv, 2, -1)
        k_scale = sc[:, :, 0, None, None, :]
        v_scale = sc[:, :, 1, None, None, :]
    qg = q.transpose(0, 2, 1, 3).reshape(s_, n_kv, g, r, d)
    s = jnp.einsum("bkgrd,bktd->bkgrt", qg, ck) / jnp.sqrt(
        jnp.asarray(d, dt))
    if k_scale is not None:
        s = (s * k_scale).astype(dt)
    at = jnp.arange(ck.shape[2])[None, None, None, None, :]
    if base is not None:
        at = at + base
    live = at <= pos[:, None, None, :, None]
    if window is not None:
        live = live & (at > pos[:, None, None, :, None] - window)
    s = jnp.where(live, s, -1e9)
    w = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        w = (w * v_scale).astype(dt)
    a = jnp.einsum("bkgrt,bktd->bkgrd", w, cv)
    return a.transpose(0, 3, 1, 2, 4).reshape(s_, r, h, d)


def _use_paged_kernel(q, pool) -> bool:
    """The dispatch line of :func:`paged_decode_attention`, decided at
    trace time from the operands: the platform gate every kernel uses
    (``kernel_registry.gate_active``), a float pool (the int8 pool's
    codes and scales keep the reference path), a head that fills whole
    128-lane tiles (K and V are lane slices of one page) or half of
    one (K and V are the halves of ONE tile: the packed form) and kv heads
    that fill whole 8-row tiles (only then is a page's
    ``[block, Hkv, 2D]`` the same bytes as the ``[block * Hkv, 2D]``
    matrix the kernel reads; otherwise XLA would re-tile the whole
    pool in front of the call)."""
    from deeplearning4j_tpu.ops.kernel_registry import gate_active
    if len(pool) != 1 or not gate_active("paged_decode"):
        return False
    kv = pool[0]
    # a folded pool's page IS the matrix the kernel reads, whatever
    # its kv heads, given whole (packed) sublane tiles a page
    whole = (kv.shape[2] % 16 == 0 if kv.ndim == 4
             else kv.shape[3] % 8 == 0)
    return (kv.dtype == q.dtype and q.dtype != jnp.float64
            and (q.shape[-1] % 128 == 0 or q.shape[-1] == 64)
            and whole)


def paged_decode_attention(q, pool, li, pt, n_live,
                           pages_per_chunk: Optional[int] = None,
                           window: Optional[int] = None,
                           n_kv: Optional[int] = None):
    """Single-token decode attention over the paged KV pool, read in
    place. ``q`` [S, H, D] (one query row per slot, RoPE applied);
    ``pool`` the pager's tuple; ``li`` the layer (Python int or i32
    scalar); ``pt`` [S, MP] i32 page table; ``n_live`` [S] i32 live
    cache positions per slot, the one just written included, 0 for an
    inactive slot. Returns [S, H, D]; an inactive slot's rows are
    zeros. Grouped-query attention shares each fetched page among the
    group's query heads. ``pages_per_chunk`` (default: 4,096 rows'
    worth) is the loop's unit; every size comes from the operands'
    shapes. Shapes the kernel does not take (:func:`_use_paged_kernel`)
    run :func:`_reference_paged_attention`. ``window``: the query (at
    ``n_live - 1``) sees the last ``window`` positions only, and the
    walk starts at the first page that holds one of them: pages
    before it are not read. Page ``p`` of a slot is then entry ``p %
    MP`` of its row of ``pt``, so a row of ``ceil(window / block) +
    1`` entries is a ring that serves a sequence of any length (the
    pager's window pages), and a row as long as the sequence is read
    as ever. A FOLDED pool ``[L, P, block * Hkv, 2D]``
    (the pager's layout for two kinds of KV pages) comes with its
    ``n_kv``."""
    from deeplearning4j_tpu.obs import devtime
    extra = {} if window is None else {"window": window}
    folded = pool[0].ndim == 4
    if folded:
        extra["n_kv"] = n_kv
    with devtime.scope("ops.paged_decode_attention"):
        if not _use_paged_kernel(q, pool):
            a = _reference_paged_attention(
                q[:, None], pool, li, pt, (n_live - 1)[:, None],
                **extra)[:, 0]
            return jnp.where((n_live > 0)[:, None, None], a,
                             jnp.zeros_like(a))
        rows = (pool[0].shape[2] if folded
                else pool[0].shape[2] * pool[0].shape[3])
        chunk = pages_per_chunk or max(1, _PAGED_CHUNK_ROWS // rows)
        return _paged_decode_call(q, pool[0], jnp.asarray(li, jnp.int32),
                                  pt, n_live,
                                  pages_per_chunk=min(chunk, pt.shape[1]),
                                  interpret=_interpret(), **extra)


# ---------------------------------------------------------------------------
# latent decode attention over the paged latent pool
# ---------------------------------------------------------------------------
#
# The latent pool (serving/kv_pager.py) is ``[L, P, block, W]``: one
# page of one layer is ``block`` positions' latent rows ``[c_kv |
# k_rope]`` (``W = kv_rank + rope``), no KV heads. The kernel is
# ``_paged_decode_kernel``'s page walk (scalar-prefetched page table
# and lengths, one contiguous DMA a page into a double buffer, float32
# online softmax) with a page used as the ``[block, W]`` matrix it is:
# ALL the absorbed queries ``[H, W]`` meet a chunk's rows in one
# matmul, and the values are the same rows' first ``kv_rank`` columns.
# There is no head to mask. ``W`` is the STORED width, whole 128-lane
# tiles (the pager pads a row's tail with zeros: the TPU tiles the
# minor dimension by 128 lanes, so a 576-wide row takes 640 in HBM
# either way, and Mosaic slices whole tiles only).
#
# As in ``_paged_decode_kernel`` the walk is ONE software pipeline
# over all (slot, chunk) items of a call, in slot order: while an
# item's rows are multiplied, the NEXT item's pages are in flight into
# the buffer's other half, be that the same slot's next chunk or the
# first chunk of the next live slot. The grid stays one step a slot
# (its output block is the slot's); the buffer, its semaphores and the
# copies in flight carry over the steps' edges. Only a call's first
# item is waited for with nothing to multiply meanwhile.

#: positions of a chunk folded per loop iteration
_LATENT_CHUNK_ROWS = 1024


def latent_chunk_pages(block: int, max_pages: int) -> int:
    """Pages of one (slot, chunk) item of the kernel's walk, from the
    operands' shapes (the scheduler counts a step's items by it)."""
    return min(max(1, _LATENT_CHUNK_ROWS // block), max_pages)


def _latent_decode_kernel(li_ref, pt_ref, n_ref, q_ref, pool_ref, o_ref,
                          buf, sem, turn, m, l, acc, *, scale: float,
                          block: int, chunk: int, max_pages: int,
                          n_pool: int, kv_rank: int):
    # li_ref [1], pt_ref [S*MP], n_ref [S]: scalar-prefetch operands in
    # SMEM; q_ref [H, W], o_ref [H, kv_rank] (this slot's blocks);
    # pool_ref [L*P, block, W], left in HBM; buf [2*chunk, block, W],
    # its halves end to end; turn [1] in SMEM: the half the next item
    # multiplied lies in (``_issue_pages`` has what a copy costs)
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    n_pos = n_ref[b]                  # live positions; 0 = inactive
    rows = chunk * block
    n_chunks = (n_pos + rows - 1) // rows
    width = q_ref.shape[1]
    pool0 = li_ref[0] * n_pool

    def n_pages(s, c):
        return jnp.minimum(chunk, (n_ref[s] + block - 1) // block
                           - c * chunk)

    def issue(s, c, half):
        # the page copies of item (slot s, chunk c) into buf's half
        _issue_pages(pt_ref, pool_ref, buf, sem.at[half], pool0,
                     s * max_pages + c * chunk, half * chunk, 0,
                     n_pages(s, c))

    @pl.when(b == 0)
    def _():
        # an unfetched tail meets p == 0 in the p·V matmul, and
        # 0 · NaN is NaN (as in ``_paged_decode_kernel``)
        buf[...] = jnp.zeros_like(buf)
        turn[0] = 0
        first = _first_live(n_ref, 0, n_slots)

        @pl.when(first < n_slots)
        def _():
            issue(first, 0, 0)

    m[...] = jnp.full_like(m, -jnp.inf)
    l[...] = jnp.zeros_like(l)
    acc[...] = jnp.zeros_like(acc)

    contract = (((1,), (1,)), ((), ()))
    # the slot whose first chunk follows this one's last; an inactive
    # slot walks nothing and looks for nothing
    after = _first_live(n_ref, jnp.where(n_pos > 0, b + 1, n_slots),
                        n_slots)
    half0 = turn[0]

    def fold(kv, left):
        # kv [R, W]: the chunk's first R rows, ``left`` of them live
        s = lax.dot_general(q_ref[...], kv, contract,
                            preferred_element_type=jnp.float32)
        rel = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # every chunk walked holds a live position, so the running
        # maximum is finite from the first on
        s = jnp.where(rel < left, s * scale, -jnp.inf)
        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l[...] = jnp.broadcast_to(
            l[:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l.shape)
        acc[...] = acc[...] * alpha + jnp.dot(
            p.astype(kv.dtype), kv[:, :kv_rank],
            preferred_element_type=jnp.float32)
        m[...] = jnp.broadcast_to(m_new, m.shape)

    # a chunk is multiplied up to the quarter that holds its last live
    # row: the matmuls' shapes are static, so each size is a branch
    sizes = _fold_sizes(chunk)

    def body(c, carry):
        half = (half0 + c) % 2
        more = c + 1 < n_chunks
        s_next = jnp.where(more, b, after)

        @pl.when(s_next < n_slots)
        def _():
            issue(s_next, jnp.where(more, c + 1, 0), 1 - half)

        pages = n_pages(b, c)
        _await_pages(pool_ref, buf, sem.at[half], pages, chunk)
        left = n_pos - c * rows
        buf0 = pl.multiple_of(half * chunk, chunk)
        for lo, hi in zip([0] + sizes, sizes):
            @pl.when((lo < pages) & (pages <= hi))
            def _(hi=hi):
                fold(buf[pl.ds(buf0, hi)].reshape(hi * block, width),
                     left)
        return carry

    lax.fori_loop(0, n_chunks, body, 0)
    turn[0] = (half0 + n_chunks) % 2
    o_ref[...] = (acc[...] / jnp.maximum(l[:, :1], 1e-30)
                  ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "kv_rank", "pages_per_chunk", "interpret"))
def _latent_decode_call(q, pool, li, pt, n_live, scale, kv_rank,
                        pages_per_chunk, interpret):
    """ONE lowering for every layer of a step (the layer index is a
    scalar operand), as ``_paged_decode_call``."""
    s_, h, width = q.shape
    n_l, n_p, block, _ = pool.shape
    mp = pt.shape[1]
    chunk = pages_per_chunk
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale,
                          block=block, chunk=chunk, max_pages=mp,
                          n_pool=n_p, kv_rank=kv_rank),
        out_shape=jax.ShapeDtypeStruct((s_, h, kv_rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s_,),
            in_specs=[pl.BlockSpec((None, h, width),
                                   lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, h, kv_rank),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2 * chunk, block, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, 128), jnp.float32),
                pltpu.VMEM((h, kv_rank), jnp.float32),
            ]),
        # the buffer and the copies in flight carry from slot to slot:
        # the grid is a sequence, not a parallel map. The compiler's
        # check of every copy's two addresses is two thirds of the
        # scalar core's work a page (22% of the kernel's time): a
        # buffer row is the loop's counter, and a page number is the
        # pager's own, held under the pool's page count by the
        # scheduler (``DecodeScheduler._check_feed``)
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True),
        interpret=interpret,
        name="latent_decode_attention",
    )(li.reshape(1).astype(jnp.int32), pt.reshape(-1).astype(jnp.int32),
      n_live.astype(jnp.int32), q,
      # the layers' pages end to end: a bitcast, not a copy
      pool.reshape(n_l * n_p, block, width))


def _reference_latent_attention(q, pool, li, pt, n_live, scale, kv_rank):
    """The absorbed latent attention of one query row a slot in plain
    jnp: gather the slot's pages through its page-table row, in
    position order, mask past its length, float32 softmax. The
    registered fallback of :func:`latent_decode_attention`. ``q``
    [S, H, W]; ``pool`` [L, P, block, W]; ``n_live`` [S] live
    positions (0: an inactive slot, zeros). Returns [S, H, kv_rank]."""
    from deeplearning4j_tpu.ops import latent
    rows = pool[li, pt].reshape(q.shape[0], -1, pool.shape[-1])
    return latent.attend_rows(q, rows.astype(q.dtype), n_live, scale,
                              kv_rank)


def _use_latent_kernel(q, pool, kv_rank: int) -> bool:
    """The dispatch line of :func:`latent_decode_attention`: the
    platform gate every kernel uses, a pool in the query's dtype, the
    stored row and the latent's lanes whole 128-lane tiles (values are
    a lane slice of a page's rows) and a page whole sublane tiles."""
    from deeplearning4j_tpu.ops.kernel_registry import gate_active
    if not gate_active("latent_decode"):
        return False
    return (pool.dtype == q.dtype and q.dtype != jnp.float64
            and kv_rank % 128 == 0 and pool.shape[3] % 128 == 0
            and pool.shape[2] % (32 // pool.dtype.itemsize) == 0)


def latent_decode_attention(q, pool, li, pt, n_live, scale: float,
                            kv_rank: int,
                            pages_per_chunk: Optional[int] = None):
    """Single-token decode attention of a latent-attention block over
    the paged latent pool, read in place, in the ABSORBED form
    (``ops/latent.py``). ``q`` [S, H, W] (``[q_nope Wk^T | q_rope]``,
    one row a slot); ``pool`` [L, P, block, W'] latent rows, stored
    ``W' >= W`` wide with a zero tail (the query is padded to match:
    zeros meet zeros); ``li`` the
    layer; ``pt`` [S, MP] i32 page table; ``n_live`` [S] i32 live
    positions a slot, the one just written included, 0 for an inactive
    slot; ``scale`` the softmax scale. Returns the weighted sums of
    latents [S, H, kv_rank]; an inactive slot's rows are zeros. Every
    head reads the same rows: a fetched page serves all H queries.
    Shapes the kernel does not take (:func:`_use_latent_kernel`) run
    :func:`_reference_latent_attention`."""
    from deeplearning4j_tpu.obs import devtime
    with devtime.scope("ops.latent_decode_attention"):
        q = jnp.pad(q, ((0, 0), (0, 0),
                        (0, pool.shape[3] - q.shape[2])))
        if not _use_latent_kernel(q, pool, kv_rank):
            return _reference_latent_attention(q, pool, li, pt, n_live,
                                               scale, kv_rank)
        chunk = min(pages_per_chunk or latent_chunk_pages(
            pool.shape[2], pt.shape[1]), pt.shape[1])
        return _latent_decode_call(
            q, pool, jnp.asarray(li, jnp.int32), pt, n_live,
            scale=float(scale), kv_rank=kv_rank, pages_per_chunk=chunk,
            interpret=_interpret())


# ---------------------------------------------------------------------------
# retention decode over the paged state pool
# ---------------------------------------------------------------------------
#
# One decode position of a power-retention block (ops/retention.py has
# the mathematics and the stored layout) for every live slot, in place:
# ``g * S + phi(k) v^T`` tile by tile for ONE kv head's state ``[rows,
# d]`` float32 at a time, the group's query heads read from the same
# tiles, the state and its normaliser ``[d, d]`` written back to their
# pages (``input_output_aliases``).
#
# The state crosses as ``_ssm_decode_kernel``'s does (the comment
# there, and PERF.md §5 "The state kernels, taken apart"): no grid, the
# call's live (slot, head) ITEMS walked in groups of ``group``, the two
# directions taking turns: group k + 1 is read, then group k written
# back while group k + 1 is multiplied. A state is ONE copy each way
# (its rows are contiguous; parts gave nothing). The multiplication
# reads one ALLOCATION and writes another, as Pallas' own pipeline had
# it: where old and new state are views of one scratch array (updated
# in place, or rotating through three buffers), the compiler cannot
# tell a tile's load from the store before it and the body runs 1.38
# ms a call for 0.90. So: two groups of buffers read into, two written
# back from, by the group's parity. An inactive slot is no item: its
# pages are neither read nor written and its output rows are zeros.
#
# The arithmetic is the VPU's: the state is float32 and a float32
# matmul on the MXU would push every state tile through it as weights,
# several passes each. A tile is 8 rows of one stored block: the rows
# ``(i, 8J .. 8J+7)``; ``k_i`` and the queries' ``q_i`` reach it as
# sublane broadcasts, ``k_j v`` and ``q_j`` as whole tiles.


def _list_live(act_ref, live):
    """Write the live slots' numbers to ``live`` (SMEM), in order;
    returns their count (the two state kernels' item list)."""
    def scan(s, m):
        @pl.when(act_ref[s] != 0)
        def _():
            live[m] = s
        return m + jnp.where(act_ref[s] != 0, 1, 0)

    return lax.fori_loop(0, act_ref.shape[0], scan, 0)


def _take_turns(n_items, group: int, copies, multiply):
    """The two state kernels' pipeline over ``n_items`` live items in
    groups of ``group``: ``copies(k, count, back, start)`` issues
    (``start``) or awaits the copies of group ``k``'s first ``count``
    items, in or ``back``; ``multiply(k, count)`` updates them in VMEM.
    Reads and write-backs take TURNS at the memory: group k - 1 goes
    back while group k is multiplied, and only when its last copy is
    out is group k + 1 fetched (two groups of buffers by a group's
    parity: group k + 1 lands where group k - 1 lay)."""
    n_groups = (n_items + group - 1) // group

    def count(k):
        return jnp.clip(n_items - k * group, 0, group)

    # the first TWO groups are read at once (nothing is there to be
    # written yet), so the first is multiplied under the second's flight
    copies(0, count(0), back=False, start=True)
    copies(1, count(1), back=False, start=True)
    copies(0, count(0), back=False, start=False)

    def phase(k, carry):
        before = jnp.where(k > 0, count(k - 1), 0)
        copies(k - 1, before, back=True, start=True)
        multiply(k, count(k))
        copies(k - 1, before, back=True, start=False)
        copies(k + 1, jnp.where(k > 0, count(k + 1), 0), back=False,
               start=True)
        copies(k + 1, count(k + 1), back=False, start=False)
        return carry

    lax.fori_loop(0, n_groups, phase, 0)
    last = jnp.where(n_groups > 0, count(n_groups - 1), 0)
    copies(n_groups - 1, last, back=True, start=True)
    copies(n_groups - 1, last, back=True, start=False)


#: bytes of state one phase moves in one direction: the (slot, head)
#: items a group are read from this against the state's shape
_RETENTION_PHASE_BYTES = 10 * 1024 * 1024


def _retention_form(n_items: int, rows: int, d: int) -> int:
    """The (slot, head) items a phase, of ``n_items`` with a state
    ``[rows, d]`` and a normaliser ``[d, d]`` float32 each."""
    group = _RETENTION_PHASE_BYTES // (4 * (rows + d) * d)
    return max(1, min(group, n_items))


def _retention_item(q_ref, k_ref, v_ref, g_ref, s_in, z_in, o_ref, s_out,
                    z_out, kb, qb, kv, *, nb: int, groups: int, eps: float):
    # one (slot, head): q_ref / o_ref [G, d], k_ref / v_ref / g_ref
    # [1, d] (g repeated along the lanes), s_in / s_out [rows, d], z_in
    # / z_out [d, d]; scratch kb [d, d] (row j = k_j on every lane), qb
    # [G, d, d] likewise, kv [d, d] (row j = k_j * v)
    d = 8 * nb
    g8 = jnp.broadcast_to(g_ref[...], (8, d))
    k_row = k_ref[...]
    kb[...] = jnp.broadcast_to(k_row, (d, d)).T
    for h in range(groups):
        qb[h] = jnp.broadcast_to(q_ref[h:h + 1, :], (d, d)).T
    kv[...] = kb[...] * v_ref[...]
    sub = lax.broadcasted_iota(jnp.int32, (8, d), 0)
    zeros = tuple(jnp.zeros((8, d), jnp.float32) for _ in range(groups))

    def tiles(base, i0, weight, acc):
        # the 8 tiles of one stored block: tile a holds the pairs
        # (i0 + a, 8J .. 8J+7); ``weight(a)`` is k_j v with the
        # write side's multiplicity
        ki = kb[pl.ds(i0, 8), :]
        qi = [qb[h, pl.ds(i0, 8), :] for h in range(groups)]
        acc = list(acc)
        for a in range(8):
            rows = pl.ds(pl.multiple_of(base + 8 * a, 8), 8)
            t = (g8 * s_in[rows, :] + jnp.broadcast_to(
                ki[a:a + 1, :], (8, d)) * weight(a))
            s_out[rows, :] = t
            for h in range(groups):
                acc[h] = acc[h] + jnp.broadcast_to(
                    qi[h][a:a + 1, :], (8, d)) * t
        return tuple(acc)

    def column(jb, out):
        j0 = pl.multiple_of(jb * 8, 8)
        kvj = kv[pl.ds(j0, 8), :]
        kvj2 = kvj + kvj
        first = jb * (jb + 1) // 2      # stored blocks before J's

        def block(ib, acc):
            return tiles((first + ib) * 64,
                         pl.multiple_of(ib * 8, 8), lambda a: kvj2,
                         acc)

        acc = lax.fori_loop(0, jb, block, zeros)
        # the diagonal block: 1 on the diagonal, 2 above it, 0 for
        # the mirror images below
        acc = tiles((first + jb) * 64, j0, lambda a: kvj * jnp.where(
            sub > a, 2.0, jnp.where(sub == a, 1.0, 0.0)), acc)
        return tuple(out[h] + qb[h, pl.ds(j0, 8), :] * acc[h]
                     for h in range(groups))

    num = lax.fori_loop(0, nb, column, zeros)
    z = (jnp.broadcast_to(g_ref[...], (d, d)) * z_in[...]
         + kb[...] * k_row)
    z_out[...] = z
    for h in range(groups):
        den = jnp.sum(jnp.sum(z * qb[h] * q_ref[h:h + 1, :], axis=0,
                              keepdims=True), axis=1, keepdims=True)
        o_ref[h:h + 1, :] = (
            jnp.sum(num[h], axis=0, keepdims=True)
            / (den + eps)).astype(o_ref.dtype)


def _retention_decode_kernel(li_ref, page_ref, act_ref, q_ref, k_ref,
                             v_ref, g_ref, s_ref, z_ref, y_ref, s_out,
                             z_out, live, s_old, z_old, s_new, z_new, sem,
                             kb, qb, kv, *,
                             nb: int, groups: int, eps: float, group: int,
                             n_pool: int):
    # li [1], page / act [S] in SMEM; q_ref / y_ref [S, Hkv, G, d],
    # k_ref / v_ref / g_ref [S, Hkv, 1, d] whole in VMEM; s_ref / s_out
    # [L * P * Hkv, rows, d] and z_ref / z_out [L * P * Hkv, d, d] in
    # HBM, ONE array each; live [S] SMEM; s_old / s_new [2, group,
    # rows, d] and z_old / z_new [2, group, d, d]: a group's states as
    # read and as updated; sem [2, 2] (the group's parity, direction)
    n_kv = q_ref.shape[1]
    pool0 = li_ref[0] * n_pool
    y_ref[...] = jnp.zeros_like(y_ref)

    def copies(k, count, back: bool, start: bool):
        half = k % 2

        def item(a, carry):
            i = k * group + a
            row = (pool0 + page_ref[live[i // n_kv]]) * n_kv + i % n_kv
            for old, new, src, dst in ((s_old, s_new, s_ref, s_out),
                                       (z_old, z_new, z_ref, z_out)):
                copy = pltpu.make_async_copy(
                    *((new.at[half, a], dst.at[row]) if back
                      else (src.at[row], old.at[half, a])),
                    sem.at[half, int(back)])
                copy.start() if start else copy.wait()
            return carry

        lax.fori_loop(0, count, item, 0)

    def multiply(k, count):
        half = k % 2

        def item(a, carry):
            i = k * group + a
            s, h = live[i // n_kv], i % n_kv
            _retention_item(
                q_ref.at[s, h], k_ref.at[s, h], v_ref.at[s, h],
                g_ref.at[s, h], s_old.at[half, a], z_old.at[half, a],
                y_ref.at[s, h], s_new.at[half, a], z_new.at[half, a], kb,
                qb, kv, nb=nb, groups=groups, eps=eps)
            return carry

        lax.fori_loop(0, count, item, 0)

    _take_turns(_list_live(act_ref, live) * n_kv, group, copies, multiply)


@functools.partial(jax.jit, static_argnames=("eps", "group", "interpret"))
def _retention_decode_call(q, k, v, g, s_pool, z_pool, li, pages, active,
                           eps, group, interpret):
    """ONE lowering for every layer of a step (the layer index is a
    scalar operand), as :func:`_paged_decode_call`."""
    n_s, n_kv, groups, d = q.shape
    n_l, n_p = s_pool.shape[:2]
    rows = s_pool.shape[3]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, s_new, z_new = pl.pallas_call(
        functools.partial(_retention_decode_kernel, nb=d // 8,
                          groups=groups, eps=eps, group=group, n_pool=n_p),
        out_shape=(jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct((n_l * n_p * n_kv, rows, d),
                                        s_pool.dtype),
                   jax.ShapeDtypeStruct((n_l * n_p * n_kv, d, d),
                                        z_pool.dtype)),
        in_specs=[smem, smem, smem, vmem, vmem, vmem, vmem, hbm, hbm],
        out_specs=(vmem, hbm, hbm),
        scratch_shapes=[pltpu.SMEM((n_s,), jnp.int32)]
        + [pltpu.VMEM((2, group, rows, d), jnp.float32),
           pltpu.VMEM((2, group, d, d), jnp.float32)] * 2
        + [pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.VMEM((d, d), jnp.float32),
                        pltpu.VMEM((groups, d, d), jnp.float32),
                        pltpu.VMEM((d, d), jnp.float32)],
        # the pools are the 8th and 9th operands
        input_output_aliases={7: 1, 8: 2},
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_retention_vmem_bytes(n_s, n_kv, groups, rows,
                                                   d, group)),
        interpret=interpret,
        name="retention_decode",
    )(li.reshape(1).astype(jnp.int32), pages.astype(jnp.int32),
      active.astype(jnp.int32), q, k[:, :, None, :], v[:, :, None, :],
      jnp.broadcast_to(g[:, :, None, None], (n_s, n_kv, 1, d)),
      # the layers' pages' heads end to end: bitcasts, not copies
      s_pool.reshape(n_l * n_p * n_kv, rows, d),
      z_pool.reshape(n_l * n_p * n_kv, d, d))
    return y, s_new.reshape(s_pool.shape), z_new.reshape(z_pool.shape)


def _retention_vmem_bytes(n_s: int, n_kv: int, groups: int, rows: int,
                          d: int, group: int) -> int:
    """The kernel's VMEM: two groups of states and normalisers as read
    and two as updated (4 x 2 x 4.52 MB at d = 128), the queries and
    the output whole and ``k``, ``v``, ``g`` a padded tile a (slot,
    head) (5 x 0.8 MB at 24 x 8), the scratch tiles, and 8 MB for the
    compiler's own."""
    small = n_s * n_kv * (2 * (-(-groups // 8) * 8) + 3 * 8) * d
    return (4 * (4 * group * (rows + d) * d + small + (groups + 2) * d * d)
            + (8 << 20))


def _reference_retention_decode(q, k, v, g, pool, li, pages, active,
                                eps):
    """One retention decode position per slot in plain jnp over the
    paged state pool: the registered fallback of
    :func:`retention_decode` (the CPU runs it, the parity test
    compares against it). An inactive slot reads and writes the trash
    page and returns zeros."""
    from deeplearning4j_tpu.ops import retention
    n_s, n_kv, groups, d = q.shape
    pids = jnp.where(active, pages, 0)
    state = (pool[0][li, pids], pool[1][li, pids])
    y, (s1, z1) = retention.retention_step(
        q.reshape(n_s, n_kv * groups, d), k, v, jnp.log(g), state, eps)
    y = jnp.where(active[:, None, None], y.astype(jnp.float32), 0.0)
    return (y.reshape(q.shape), pool[0].at[li, pids].set(s1),
            pool[1].at[li, pids].set(z1))


def _use_retention_kernel(q) -> bool:
    """The dispatch line of :func:`retention_decode`: the platform gate
    every kernel uses, and a head that fills whole 128-lane tiles (the
    state's minor dimension is the value head's width)."""
    from deeplearning4j_tpu.ops.kernel_registry import gate_active
    return gate_active("retention_decode") and q.shape[-1] % 128 == 0


def retention_decode(q, k, v, g, pool, li, pages, active,
                     eps: Optional[float] = None):
    """One decode position of a power-retention block for every slot,
    the recurrent state updated in place in the paged pool. ``q``
    [S, H, d] and ``k``/``v`` [S, Hkv, d] (normalised, rotated), ``g``
    [S, Hkv] float32 in (0, 1); ``pool`` the pager's ``(S, Z)`` arrays
    (``serving/kv_pager.py``: ``[L, P, Hkv, rows, d]`` and
    ``[L, P, Hkv, d, d]`` float32); ``li`` the layer (Python int or
    i32 scalar); ``pages`` [S] i32 each slot's state page; ``active``
    [S] bool. Returns ``(y [S, H, d] in q's dtype, pool)``; an inactive
    slot's rows are zeros and its page is neither read nor written.
    Shapes the kernel does not take (:func:`_use_retention_kernel`)
    run :func:`_reference_retention_decode`."""
    from deeplearning4j_tpu.obs import devtime
    from deeplearning4j_tpu.ops.retention import RETENTION_EPS
    from deeplearning4j_tpu.perf import sentry
    eps = RETENTION_EPS if eps is None else float(eps)
    n_s, n_h, d = q.shape
    n_kv = k.shape[1]
    with devtime.scope("ops.retention_decode"):
        args = (q.reshape(n_s, n_kv, n_h // n_kv, d).astype(jnp.float32),
                k.astype(jnp.float32), v.astype(jnp.float32),
                g.astype(jnp.float32))
        if _use_retention_kernel(q):
            group = _retention_form(n_s * n_kv, *pool[0].shape[3:])
            sentry.note_traced(
                "retention_decode_kernels",
                state_bytes=4 * (pool[0].shape[3] + d) * d, state_parts=1,
                state_buffers=4 * group)
            y, s_pool, z_pool = _retention_decode_call(
                *args, pool[0], pool[1], jnp.asarray(li, jnp.int32),
                pages, active, eps=eps, group=group,
                interpret=_interpret())
        else:
            y, s_pool, z_pool = _reference_retention_decode(
                *args, pool, li, pages, active, eps)
        return y.reshape(q.shape).astype(q.dtype), (s_pool, z_pool)


# ---------------------------------------------------------------------------
# Mamba-2 decode over the paged state pool (a hybrid decoder's step)
# ---------------------------------------------------------------------------
#
# One decode position of a Mamba-2 layer (ops/ssm.py has the
# mathematics and the stored layout) for every live slot, in place:
# ``a H + B (x) (Delta x)`` a 128-lane column block at a time, each
# contracted with ``C`` over its rows, the state ``[N, H P]`` float32
# read from its page and written back to it (``input_output_aliases``).
#
# The call is bound by the memory's bandwidth (2.6 MFLOP against 4.2 MB
# moved a slot and layer), and what holds it is how the two directions
# SHARE the memory (PERF.md §5, "The state kernels, taken apart"): a
# read stream and a write stream in flight together reach 79% of the
# bandwidth whatever the copies' number or size (XLA's own elementwise
# pass in place: 80%), read alone 87% and written alone 83%. So the
# directions take TURNS. The kernel has no grid: it lists the call's
# live slots and walks them in GROUPS of ``group`` slots, ONE pipeline
# over the call:
#
#   read group k + 1   |  (the core waits)
#   write group k      |  group k + 1 is multiplied, in its buffer
#   read group k + 2   |  ...
#
# Each state crosses as ``parts`` column ranges, a copy each (a write
# of 128 rows x 2 KB is faster than one of 2 MB on end), all of a
# phase's copies in flight at once; the next phase's are issued when
# the last of this one's has landed. Two groups of buffers, updated in
# place. An inactive slot is not on the list: it costs the scan's
# compare, its page is neither read nor written, its output row is
# zeros. The small operands and the output lie whole in VMEM.
#
# The arithmetic is the VPU's. The state's lanes are (head, feature)
# columns, so the decay, ``Delta x`` and the output are lane rows as
# the caller has them; only ``B`` and ``C``, one a slot, turn into
# sublane columns (a broadcast and ONE square transpose each).

#: lanes of one column block of the state
_SSM_COLS = 128
#: lanes of one copy of a slot's state (a column range of every row)
_SSM_PART_COLS = 512
#: bytes of state one phase moves in one direction: the slots a group
#: are read from this against the state's shape. PERF.md §5 has the
#: sweep at the serving cell's shapes
_SSM_PHASE_BYTES = 16 * 1024 * 1024


def _ssm_form(n_s: int, n: int, cols: int):
    """``(group, parts)`` of ``n_s`` slots' states ``[n, cols]``
    float32: the slots a phase and the copies a slot."""
    parts = cols // _SSM_PART_COLS if cols % _SSM_PART_COLS == 0 else 1
    group = _SSM_PHASE_BYTES // (4 * n * cols)
    return max(1, min(group, n_s)), parts


def _ssm_decode_kernel(li_ref, page_ref, act_ref, b_ref, c_ref, decay_ref,
                       dx_ref, pool_ref, y_ref, pool_out, live, buf, sem,
                       dec, dxs, ys, *, n: int, cols: int, group: int,
                       parts: int, n_pool: int):
    # li [1], page / act [S] in SMEM; b_ref / c_ref [S, N], decay_ref /
    # dx_ref / y_ref [S, H P] whole in VMEM; pool_ref / pool_out
    # [L * P, N, H P] in HBM, ONE array; live [S] SMEM; buf [2, group,
    # parts, N, H P / parts]; sem [2, 2] (half, direction); dec / dxs /
    # ys [1, H P]: the slot's rows, staged
    w = cols // parts
    pool0 = li_ref[0] * n_pool
    y_ref[...] = jnp.zeros_like(y_ref)

    def copies(k, count, back: bool, start: bool):
        half = k % 2

        def slot(a, carry):
            row = pool0 + page_ref[live[k * group + a]]

            def part(p, carry):
                at = pl.ds(pl.multiple_of(p * w, _SSM_COLS), w)
                here = buf.at[half, a, p]
                copy = pltpu.make_async_copy(
                    *((here, pool_out.at[row, :, at]) if back
                      else (pool_ref.at[row, :, at], here)),
                    sem.at[half, int(back)])
                copy.start() if start else copy.wait()
                return carry

            return lax.fori_loop(0, parts, part, carry)

        lax.fori_loop(0, count, slot, 0)

    def multiply(k, count):
        half = k % 2

        def slot(a, carry):
            s = live[k * group + a]
            bcol = jnp.broadcast_to(b_ref[pl.ds(s, 1), :], (n, n)).T
            ccol = jnp.broadcast_to(c_ref[pl.ds(s, 1), :], (n, n)).T
            # a row of a slot only the call knows is loaded WHOLE:
            # Mosaic takes no lane slice at a dynamic sublane
            dec[...] = decay_ref[pl.ds(s, 1), :]
            dxs[...] = dx_ref[pl.ds(s, 1), :]

            def part(p, carry):
                def column(j, carry):
                    o = pl.multiple_of(j * _SSM_COLS, _SSM_COLS)
                    at = pl.ds(pl.multiple_of(p * w + o, _SSM_COLS),
                               _SSM_COLS)
                    here = pl.ds(o, _SSM_COLS)
                    new = (jnp.broadcast_to(dec[:, at], (n, _SSM_COLS))
                           * buf[half, a, p, :, here]
                           + bcol * jnp.broadcast_to(dxs[:, at],
                                                     (n, _SSM_COLS)))
                    buf[half, a, p, :, here] = new
                    ys[:, at] = jnp.sum(new * ccol, axis=0, keepdims=True)
                    return carry

                return lax.fori_loop(0, w // _SSM_COLS, column, carry)

            lax.fori_loop(0, parts, part, 0)
            y_ref[pl.ds(s, 1), :] = ys[...]
            return carry

        lax.fori_loop(0, count, slot, 0)

    _take_turns(_list_live(act_ref, live), group, copies, multiply)


@functools.partial(jax.jit, static_argnames=("group", "parts", "interpret"))
def _ssm_decode_call(b, c, decay, dx, pool, li, pages, active, group, parts,
                     interpret):
    """ONE lowering for every Mamba layer of a step (the layer index is
    a scalar operand), as :func:`_paged_decode_call`."""
    n_s, n = b.shape
    cols = dx.shape[1]
    n_l, n_p = pool.shape[:2]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    y, new = pl.pallas_call(
        functools.partial(_ssm_decode_kernel, n=n, cols=cols, group=group,
                          parts=parts, n_pool=n_p),
        out_shape=(jax.ShapeDtypeStruct((n_s, cols), jnp.float32),
                   jax.ShapeDtypeStruct((n_l * n_p, n, cols), pool.dtype)),
        in_specs=[smem, smem, smem, vmem, vmem, vmem, vmem, hbm],
        out_specs=(vmem, hbm),
        scratch_shapes=[
            pltpu.SMEM((n_s,), jnp.int32),
            pltpu.VMEM((2, group, parts, n, cols // parts), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ] + [pltpu.VMEM((1, cols), jnp.float32)] * 3,
        # the pool is the 8th operand
        input_output_aliases={7: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=_ssm_vmem_bytes(n_s, n, cols, group)),
        interpret=interpret,
        name="ssm_decode",
    )(li.reshape(1).astype(jnp.int32), pages.astype(jnp.int32),
      active.astype(jnp.int32), b, c, decay, dx,
      # the layers' pages end to end: a bitcast, not a copy
      pool.reshape(n_l * n_p, n, cols))
    return y, new.reshape(pool.shape)


def _ssm_vmem_bytes(n_s: int, n: int, cols: int, group: int) -> int:
    """The kernel's VMEM: two groups of states (2 x 8 x 2.1 MB at 128 x
    4096), ``decay``, ``dx`` and the output whole (3 x 1 MB at 64
    slots), ``B`` and ``C``, the staged rows, and 8 MB for the
    compiler's own (the two transposes' tiles)."""
    rows = -(-n_s // 8) * 8
    return (4 * (2 * group * n * cols + 3 * rows * cols + 2 * rows * n
                 + 3 * 8 * cols) + (8 << 20))


def _reference_ssm_decode(b, c, decay, dx, pool, li, pages, active):
    """One Mamba-2 decode position per slot in plain jnp over the paged
    state pool: the registered fallback of :func:`ssm_decode` (the CPU
    runs it, the parity test compares against it). An inactive slot
    reads and writes the trash page and returns zeros."""
    pids = jnp.where(active, pages, 0)
    new = (decay[:, None, :] * pool[li, pids]
           + b[:, :, None] * dx[:, None, :])
    y = jnp.sum(new * c[:, :, None], axis=1)
    return (jnp.where(active[:, None], y, 0.0),
            pool.at[li, pids].set(new))


def _use_ssm_kernel(b, dx) -> bool:
    """The dispatch line of :func:`ssm_decode`: the platform gate every
    kernel uses, a state of 128 values a column (``B`` and ``C`` turn
    into columns by one square transpose) and whole 128-lane column
    blocks."""
    from deeplearning4j_tpu.ops.kernel_registry import gate_active
    return (gate_active("ssm_decode") and b.shape[-1] == _SSM_COLS
            and dx.shape[-1] % _SSM_COLS == 0)


def ssm_decode(x, b, c, delta, a_neg, d_skip, pool, li, pages, active):
    """One decode position of a Mamba-2 layer for every slot, the
    float32 state updated in place in the paged pool. ``x`` [S, H P]
    (convolved), ``b``/``c`` [S, N], ``delta`` [S, H] float32 (after
    the softplus), ``a_neg``/``d_skip`` [H]; ``pool`` the pager's state
    array ``[L, P, N, H P]`` float32 (``serving/kv_pager.py``); ``li``
    the layer's index in the pool (Python int or i32 scalar); ``pages``
    [S] i32 each slot's state page; ``active`` [S] bool. Returns ``(y
    [S, H P] float32, pool)``: ``y = H_t C + D x``; an inactive slot's
    row is zeros and its page is neither read nor written. Shapes the
    kernel does not take (:func:`_use_ssm_kernel`) run
    :func:`_reference_ssm_decode`."""
    from deeplearning4j_tpu.obs import devtime
    from deeplearning4j_tpu.perf import sentry
    with devtime.scope("ops.ssm_decode"):
        p = x.shape[-1] // delta.shape[-1]
        xf = x.astype(jnp.float32)
        args = (b.astype(jnp.float32), c.astype(jnp.float32),
                jnp.repeat(jnp.exp(delta * a_neg), p, axis=-1),
                jnp.repeat(delta, p, axis=-1) * xf)
        if _use_ssm_kernel(args[0], args[3]):
            group, parts = _ssm_form(x.shape[0], *pool.shape[2:])
            sentry.note_traced(
                "ssm_decode_kernels", state_bytes=4 * pool.shape[2] * pool.shape[3],
                state_parts=parts, state_buffers=2 * group)
            y, pool = _ssm_decode_call(
                *args, pool, jnp.asarray(li, jnp.int32), pages, active,
                group=group, parts=parts, interpret=_interpret())
        else:
            y, pool = _reference_ssm_decode(*args, pool, li, pages,
                                            active)
        skip = jnp.repeat(d_skip.astype(jnp.float32), p, axis=-1) * xf
        return y + jnp.where(active[:, None], skip, 0.0), pool


# ---------------------------------------------------------------------------
# threshold compression codec
# ---------------------------------------------------------------------------
_GROUP = 16          # 16 two-bit codes per int32 word
_BLOCK_COLS = 32768  # grid block width (16x32768 f32 = 2 MB VMEM)


def _encode_kernel(g_ref, tau_ref, packed_ref, resid_ref):
    tau = tau_ref[0]
    g = g_ref[:]                               # (16, C)
    code = jnp.where(g > tau, 1, jnp.where(g < -tau, 2, 0))
    q = jnp.where(g > tau, tau, jnp.where(g < -tau, -tau, 0.0))
    resid_ref[:] = g - q
    shifts = 2 * lax.broadcasted_iota(jnp.int32, g.shape, 0)
    packed_ref[:] = jnp.sum(code.astype(jnp.int32) << shifts, axis=0,
                            keepdims=True)


def _decode_kernel(p_ref, tau_ref, out_ref):
    tau = tau_ref[0]
    shifts = 2 * lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    code = (p_ref[:] >> shifts) & 3            # broadcast (1,C)->(16,C)
    out_ref[:] = jnp.where(code == 1, tau,
                           jnp.where(code == 2, -tau, 0.0))


def _jnp_threshold_encode(g2, tau, size, shape):
    """jnp fallback of :func:`threshold_encode` over the padded
    ``(16, C)`` group layout — used under shard_map-on-CPU (interpret
    mode cannot run there) and declared in
    ``ops/kernel_registry.py``."""
    g2 = g2.astype(jnp.float32)
    tau_f = jnp.asarray(tau, jnp.float32)
    code = jnp.where(g2 > tau_f, 1, jnp.where(g2 < -tau_f, 2, 0))
    qv = jnp.where(g2 > tau_f, tau_f,
                   jnp.where(g2 < -tau_f, -tau_f, 0.0))
    shifts = 2 * jnp.arange(_GROUP, dtype=jnp.int32)[:, None]
    packed = jnp.sum(code.astype(jnp.int32) << shifts, axis=0,
                     keepdims=True)
    resid = g2 - qv
    residual = resid.T.reshape(-1)[:size].reshape(shape)
    return packed[0], residual


def threshold_encode(grad: jax.Array, tau):
    """Fused threshold encode: grad → (packed int32 codes, residual).

    Reference op ``encode_threshold`` (+ residual handling of
    ``EncodedGradientsAccumulator``): q = τ·sign(g)·1[|g|>τ]; 2 bits
    per element (code 0 / +τ=1 / −τ=2), residual = g − q.
    """
    from deeplearning4j_tpu.obs import devtime
    shape, size = grad.shape, grad.size
    flat = grad.reshape(-1)
    c = -(-size // _GROUP)
    c = -(-c // 128) * 128                     # lane-align columns
    flat = jnp.pad(flat, (0, _GROUP * c - size))
    g2 = flat.reshape(c, _GROUP).T             # (16, C), flat-major groups
    tau_arr = jnp.asarray([tau], jnp.float32)
    bc = min(c, _BLOCK_COLS)
    c = -(-c // bc) * bc
    g2 = jnp.pad(g2, ((0, 0), (0, c - g2.shape[1])))
    if _jnp_fallback(grad):
        return _jnp_threshold_encode(g2, tau, size, shape)
    tau_arr = _align_vma(tau_arr, _vma(grad))
    with devtime.scope("ops.threshold_encode"):
        packed, resid = pl.pallas_call(
            _encode_kernel,
            out_shape=(_sds((1, c), jnp.int32, _vma(grad)),
                       _sds((_GROUP, c), jnp.float32, _vma(grad))),
            grid=(c // bc,),
            in_specs=[pl.BlockSpec((_GROUP, bc), lambda i: (0, i)),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=(pl.BlockSpec((1, bc), lambda i: (0, i)),
                       pl.BlockSpec((_GROUP, bc), lambda i: (0, i))),
            interpret=_interpret(),
        )(g2.astype(jnp.float32), tau_arr)
    residual = resid.T.reshape(-1)[:size].reshape(shape)
    return packed[0], residual


def _jnp_threshold_decode(packed, tau, size, shape):
    """jnp fallback of :func:`threshold_decode` (shard_map-on-CPU;
    declared in ``ops/kernel_registry.py``)."""
    tau_f = jnp.asarray(tau, jnp.float32)
    shifts = 2 * jnp.arange(_GROUP, dtype=jnp.int32)[:, None]
    code = (packed[None, :] >> shifts) & 3
    out = jnp.where(code == 1, tau_f,
                    jnp.where(code == 2, -tau_f, 0.0))
    dense = out.T.reshape(-1)[:size]
    return dense.reshape(shape) if shape is not None else dense


def threshold_decode(packed: jax.Array, tau, size: int, shape=None):
    """Reference op ``decode_threshold``: packed codes → dense ±τ."""
    from deeplearning4j_tpu.obs import devtime
    c0 = packed.shape[0]
    bc = min(c0, _BLOCK_COLS)
    c = -(-c0 // bc) * bc
    packed = jnp.pad(packed, (0, c - c0))
    if _jnp_fallback(packed):
        return _jnp_threshold_decode(packed, tau, size, shape)
    tau_arr = _align_vma(jnp.asarray([tau], jnp.float32), _vma(packed))
    with devtime.scope("ops.threshold_decode"):
        out = pl.pallas_call(
            _decode_kernel,
            out_shape=_sds((_GROUP, c), jnp.float32, _vma(packed)),
            grid=(c // bc,),
            in_specs=[pl.BlockSpec((1, bc), lambda i: (0, i)),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec((_GROUP, bc), lambda i: (0, i)),
            interpret=_interpret(),
        )(packed.reshape(1, c), tau_arr)
    dense = out.T.reshape(-1)[:size]
    return dense.reshape(shape) if shape is not None else dense

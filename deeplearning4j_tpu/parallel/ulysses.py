"""Ulysses-style sequence parallelism — all-to-all head/sequence swap.

NEW capability vs the reference (same SURVEY §5 long-context mandate as
``ring_attention``, second strategy): instead of rotating KV blocks
around the ICI ring, the mesh's ``seq`` axis is traded for the HEAD
axis around attention — an ``all_to_all`` regathers the full sequence
per device while scattering heads (DeepSpeed-Ulysses / GSPMD pattern):

    [B, T/N, H, D]  --all_to_all-->  [B, T, H/N, D]
        (attention with full sequence, 1/N of the heads)
    [B, T, H/N, D]  --all_to_all-->  [B, T/N, H, D]

Two all-to-alls per attention call (O(B·T·H·D/N) bytes each, riding
ICI) versus ring attention's N ppermute rounds; Ulysses wins when the
head count ≥ mesh size and sequences are long enough that ring-step
latency dominates.  Memory: activations stay O(T/N) per device outside
the attention call; *inside* it each device attends over the full
sequence with H/N heads through ``scaled_dot_attention`` — on TPU at
T ≥ DL4J_TPU_FLASH_MIN_T that takes the Pallas flash path, masked or
not (the kernel carries a per-example key-mask operand), so no [T,T]
scores are materialised; only sub-threshold sequences use the einsum
path's [B, H/N, T, T] tile.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def ulysses_self_attention(q, k, v, mesh: Mesh,
                           axis_name: str = "seq",
                           mask: Optional[jax.Array] = None,
                           causal: bool = False):
    """Distributed attention: inputs [B, T, H, D] sharded on T over
    ``axis_name``; returns [B, T, H, D] with identical sharding.

    Requires ``H % mesh.shape[axis_name] == 0`` (heads redistribute
    across the axis).  ``mask``: [B, T] key mask, sharded like the
    inputs.  Cites reference parity point: SURVEY §5 long-context row
    (the reference has no sequence-parallel attention; this and
    ``ring_attention`` are the rebuild's two strategies).
    """
    n = mesh.shape[axis_name]
    h = q.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the "
            f"{axis_name!r} axis size ({n}); use ring_attention for "
            "head counts below the mesh size")

    def local(q, k, v, kmask):
        from deeplearning4j_tpu.nn.layers.attention import \
            scaled_dot_attention

        # [B, T/N, H, D] -> [B, T, H/N, D]: concat sequence shards,
        # scatter head shards
        def seq_to_head(x):
            return lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

        def head_to_seq(x):
            return lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

        qf, kf, vf = seq_to_head(q), seq_to_head(k), seq_to_head(v)
        mf = (lax.all_gather(kmask, axis_name, axis=1, tiled=True)
              if kmask is not None else None)
        out = scaled_dot_attention(qf, kf, vf, mask=mf, causal=causal)
        return head_to_seq(out)

    spec = P(None, axis_name, None, None)
    mspec = P(None, axis_name)
    args = [q, k, v]
    if mask is not None:
        return shard_map(local, mesh=mesh,
                         in_specs=(spec, spec, spec, mspec),
                         out_specs=spec, check_vma=False)(*args, mask)
    return shard_map(lambda a, b, c: local(a, b, c, None), mesh=mesh,
                     in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)(q, k, v)

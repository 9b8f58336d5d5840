"""Paged/block KV cache — the serving gateway's memory plane.

The dense decode path (``zoo/gpt.py::_decode_gen``) builds one KV
cache of ``[B, Hkv, 2D, tb + n_new]`` per layer *per generate() call*:
cache memory is O(batch x max_len) whether or not the sequences use
it, and a new sequence can only join by retracing a new batch shape.
This module replaces that with the vLLM-style paged layout the
compiler-first O(1)-per-token caching design calls for (PAPERS.md:
arxiv 2603.09555): a FIXED pool of ``block``-token pages, a
per-sequence page table, and free-list allocation — cache memory is
O(active tokens) (rounded up to page granularity), sequences of any
length share one pool, and the pool's shape never changes, so the
decode step compiles exactly once.

Layout (one layer-stacked array pair, the tuple the jitted step
carries as its donated pool argument):

- ``codes``  ``[L, P, block, Hkv, 2D]`` — page ``p`` of layer ``l``
  holds ``block`` consecutive positions, each position the k (lanes
  ``0:D``) and v (lanes ``D:2D``) rows of every kv head. The head
  dimension is minor and a page is ONE contiguous run
  (``block * Hkv * 2D`` elements, 64 KB for 16 x 8 x 256 in bf16), so
  the decode step's kernel (``ops.paged_decode_attention``) copies
  whole pages HBM -> VMEM through the page table and reads nothing
  else; a position's ``[Hkv, 2D]`` is one whole (8, 128)-tiled run
  too, which is what lets XLA scatter each step's new KV row IN
  PLACE (with the kv heads outside the positions,
  ``[L, P, Hkv, block, 2D]``, the TPU compiler re-tiled the whole pool
  around every layer's scatter: seven 2 GB copies a step, read off the
  compiled step, PR 25). The pool takes exactly its
  ``L * P * block * Hkv * 2D`` elements on the device, for 8 KV
  heads and for 4 alike (the compiler tiles the two minor dimensions
  by ``Hkv`` rows where ``Hkv`` is under 8; read off the compiled
  programs' argument bytes and the chip's, PR 46); but only with
  ``Hkv`` a multiple of 8 is a page the ``[block * Hkv, 2D]`` matrix
  the decode kernel reads, byte for byte: see the FOLDED layout
  below. (The previous
  layout, ``[L, P, Hkv, 2D, block]``, was the dense cache's: the
  minor dimension was the page's 16 positions, an eighth of a
  128-lane tile.) dtype is ``int8`` under ``cache_quant="int8"``
  (codes from ``nn.decoder_infer.quant_kv``, the same quantiser the
  dense path uses — the pager-correctness fence demands token
  identity), else the model's compute dtype.
- ``scales`` ``[L, P, Hkv, 2, block]`` f32 — per-(page, head, k/v
  half, position) dequant scales; present only under int8. Positions
  stay minor here: a minor dimension of 2 would pad every pair of
  scales to a 128-lane row.

A model whose blocks keep a recurrent state instead of a KV cache
(``CausalTransformerLM(mixer="power_retention")``) gets a pool of
**fixed-size state pages** from the same pager (``state_rows``): ONE
page a sequence whatever its length, the pair

- ``S`` ``[L, P, Hkv, rows, d]`` float32: page ``p`` of layer ``l``
  holds each kv head's second-power state in the stored layout of
  ``ops/retention.py`` (``rows`` = ``retention.state_rows(d)``), one
  head's ``[rows, d]`` a contiguous run of whole (8, 128) tiles, which
  is what ``ops.retention_decode`` streams through VMEM and writes
  back in place;
- ``Z`` ``[L, P, Hkv, d, d]`` float32: the normaliser.

Allocation, reservation, the free list and every invariant below are
the same; ``pages_for`` answers 1, and nothing is ever shared (a
state is no pure function of a prefix's tokens alone that another
sequence could adopt mid-way: there is no chain index to consult).

A model whose blocks attend through a latent
(``CausalTransformerLM(mixer="latent")``, ``ops/latent.py``) gets the
third kind of page (``latent_dim``): ONE compressed row a position and
no KV heads,

- ``rows`` ``[L, P, block, W']`` in the compute dtype: page ``p`` of
  layer ``l`` holds ``block`` consecutive positions' ``[c_kv (normed)
  | k_rope (rotated)]``, the ``[block, W']`` matrix that
  ``ops.latent_decode_attention`` reads with every head at once.
  ``W'`` is ``latent_dim`` rounded up to whole 128-lane tiles, the
  tail zero (``ops.latent.lanes``): the TPU tiles the minor
  dimension by 128 lanes, so a 576-wide row takes 640 in HBM whatever
  the shape says, and the kernel's DMAs move whole tiles only. Bytes
  are counted by ``latent_dim``.

Pages are counted, reserved and freed as KV pages are (``pages_for``
by ``block``); they are not shared and not quantised yet (the
scheduler refuses both for such a model).

A HYBRID decoder (``CausalTransformerLM(mixer="hybrid")``: Mamba-2
state-space layers beside softmax attention layers, ``ops/ssm.py``)
holds TWO kinds of per-sequence state in this one pager (``ssm``), the
pool tuple ``(kv, H, tail)``, each array stacked over the layers of ITS
kind only (a ``[n_layers, ...]`` of each would pay every kind's bytes
for every layer):

- ``kv`` ``[L_attn, P, block, Hkv, 2D]``: the KV pages above, for the
  attention layers; allocated, reserved and freed by the free list;
- ``H`` ``[L_ssm, 1 + slots, N, H P]`` float32: a sequence's state in
  each Mamba layer, the stored matrix of ``ops/ssm.py`` (row ``n`` the
  state value ``n`` of every (head, feature) column), which
  ``ops.ssm_decode`` streams through VMEM and writes back in place;
- ``tail`` ``[L_ssm, 1 + slots, (K - 1) * channels]`` in the compute
  dtype: the last ``K - 1`` un-convolved rows of each layer's
  convolution, flat (one page is one lane-aligned run).

A sequence's ONE state page is ``slot + 1`` (page 0 the trash page):
there are exactly as many as decode slots, so the slot IS the
reservation and the state side has no free list, no refcount and no
invariant of its own; what can leak is a KV page, and ``free_pages``
counts those. A state page comes to its next sequence as its last one
left it: admission starts from an empty state when ``start`` is 0.

A WINDOWED decoder (``CausalTransformerLM(window=...)``: softmax
layers of two kinds, ``decoder_infer.WindowSpec``) holds KV pages of
TWO kinds in this one pager (``windowed``), the pool tuple ``(kv_full,
kv_window)``, each stacked over the layers of its kind, both FOLDED:

- ``kv_full`` ``[L_full, P, block * Hkv, 2D]``: a full layer keeps
  every position; pages off the free list, reserved at admission for
  the sequence's whole life, as the KV pages above;
- ``kv_window`` ``[L_window, 1 + slots * ring, block * Hkv, 2D]``,
  ``ring = ceil(window / block) + 1``: a window layer's query sees the
  last ``window`` keys, which lie in at most ``ring`` pages, so decode
  slot ``s`` OWNS the ``ring`` pages from ``1 + s * ring`` on and
  writes them as a ring: position ``t`` goes to the slot's page
  ``(t // block) % ring``, over the page that held positions ``ring *
  block`` earlier, all of them out of every later query's window. The
  slot IS the reservation (as a hybrid's state page is): no free list,
  no refcount, no release in mid-flight, and a sequence can never hold
  more than ``ring`` pages of a window layer. (A second free list used
  as a ring would let short sequences leave pages to long ones; at 48
  slots the ring rows are 2.4 GB of 4.3 GB of pool, and what a short
  sequence leaves unused no admission could take without a release in
  mid-flight, which the scheduler's whole-life reservation rules out.)
  The step reads a ring through the slot's own ``ring`` entries of a
  page table (:class:`PagedWindowKV`), which
  ``ops.paged_decode_attention(window=)`` reads modulo their number.

A folded page ``[block * Hkv, 2D]`` is the matrix
``ops.paged_decode_attention`` reads, stored as that: positions and
kv heads share the sublane dimension, so the bytes are the plain ones
whatever ``Hkv`` is. The unfolded ``[block, Hkv, 2D]`` is the same
bytes only where ``Hkv`` fills whole 8-row tiles: for 4 KV heads the
TPU compiler gives it 4-row tiles (``T(4,128)(2,1)`` in bf16: still
the plain bytes, no padding), whose order in memory is not the
matrix's (``T(8,128)(2,1)``), and the kernel's view of a page would be
a copy of the whole pool in front of every call.
``prefix_sharing``, ``spec_k`` and ``cache_quant`` are refused for
such a model: a shared page that a ring overwrites, a rejected draft's
row that has already overwritten a visible one, an int8 ring.

Page 0 is the reserved **trash page**: inactive slots' writes and
unallocated page-table entries route there, so a fixed-shape step can
always scatter/gather without corrupting live sequences (reads of
trash positions are masked by each slot's length).

Pages are REFCOUNTED: several live sequences may reference the same
physical page (copy-on-write prefix sharing — a KV page is a pure
function of the tokens it covers, so requests that share a prompt
prefix can share its pages byte-for-byte). The pager keeps a
content-addressed **page-chain index** keyed by the token bytes each
full-page prefix covers: admission hashes the prompt's page chain
(:meth:`KVPager.match_prefix`), adopts the shared pages with
:meth:`KVPager.adopt` (refcount bump, no prefill), and the scheduler
copies a page before writing it whenever its refcount exceeds one
(:meth:`KVPager.cow` does the bookkeeping; the device copy is
:meth:`KVPager.copy_page` inside the scheduler's sentried page-copy
program). A page returns to the free list only when its LAST
reference releases.

The pager itself is host-side bookkeeping: free list, per-page
refcounts, per-owner page lists, the chain index, and the invariants
the tests fence (refcount conservation — the sum of live table
references per page equals its refcount, trash page exempt — no page
both free and referenced, allocation conservation). The device arrays
live here too so the scheduler can thread them through its jitted
step and write the updated pool back.

The layouts above are known HERE and to the kernels that read them
(``ops/pallas_kernels.py``), nowhere else: a program of the scheduler
reaches its pool through the **cache objects** at the end of this
file (``nn/decoder_infer.py``'s contract, ``attend(li, mha, h)``):
:meth:`KVPager.rows` (R rows a slot) builds the ONE class the pager
chose with its pool, :attr:`KVPager.cache` (:class:`PagedKV`,
:class:`PagedState`, :class:`PagedLatent`, or for a hybrid
:class:`PagedHybrid`, which goes PER LAYER by the layer's kind,
``decoder_infer.ByKind``), and through :meth:`KVPager.write_prompt`
and :meth:`KVPager.copy_page`. What else depends on the kind of page
the class says itself: whether a step walks KV pages, which arrays of
the pool are recurrent state, and the cache class of chunk admission
(``cache.chunk``: :class:`StateChunk`, :class:`HybridChunk`), which in
turn says where it finds a sequence's state (``where``) and what a
prompt's chunks hand on beside the pool (``carried``). A windowed
decoder's class is :class:`PagedWindowed` (``ByKind`` over
:class:`PagedKV` and :class:`PagedWindowKV`), and its bucket prefill's
pages come from :meth:`KVPager.prompt_pages`.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.nn.layers.core import RMSNORM_EPS
from deeplearning4j_tpu.obs import devtime
from deeplearning4j_tpu.obs import metrics as _metrics
from deeplearning4j_tpu.ops import latent, retention, ssm
from deeplearning4j_tpu.ops.pallas_kernels import (
    _reference_paged_attention, latent_decode_attention,
    paged_decode_attention, retention_decode, ssm_decode)


class PageTableError(RuntimeError):
    """A pager invariant broke (page referenced without a matching
    refcount, free-list leak, double free) — raised by
    :meth:`KVPager.check_invariants`, the churn tests' fence."""


def ring_pages(window: int, block: int) -> int:
    """Pages of a window layer's ring: the ``window`` keys a query
    sees lie in at most ``ceil(window / block) + 1`` pages."""
    return -(-window // block) + 1


class KVPager:
    """Fixed pool of refcounted KV pages with free-list allocation.

    ``n_pages`` counts the trash page: usable capacity is
    ``n_pages - 1`` pages of ``block`` tokens each. The pool holds one
    of four kinds of pages, chosen here once: KV pages (``(codes,)``,
    or ``(codes, scales)`` under ``cache_quant``), recurrent-state
    pages (``state_rows``: ``(S, Z)``), latent pages (``latent_dim``:
    ``(rows,)``), or a hybrid decoder's KV pages beside its state
    pages (``ssm``: ``(kv, H, tail)``; ``n_layers`` then counts the
    attention layers and ``ssm`` is ``(an ops.ssm.HybridSpec, decode
    slots)``), or a windowed decoder's two kinds of KV pages
    (``windowed``: ``(kv_full, kv_window)``, both folded; ``n_layers``
    then counts the FULL layers and ``windowed`` is ``(a
    decoder_infer.WindowSpec, decode slots)``).
    """

    def __init__(self, *, n_layers: int, n_kv_heads: int, head_dim: int,
                 n_pages: int, block: int, cache_quant: Optional[str],
                 dtype: str = "float32",
                 state_rows: Optional[int] = None,
                 latent_dim: Optional[int] = None,
                 ssm: Optional[Tuple] = None,
                 windowed: Optional[Tuple] = None):
        if windowed is not None and (
                cache_quant is not None or state_rows is not None
                or latent_dim is not None or ssm is not None):
            raise ValueError("a windowed pool holds float KV pages of "
                             "two kinds: cache_quant, state_rows, "
                             "latent_dim and ssm do not apply")
        if ssm is not None and (cache_quant is not None
                                or state_rows is not None
                                or latent_dim is not None):
            raise ValueError("a hybrid pool holds float KV pages beside "
                             "float32 state pages: cache_quant, "
                             "state_rows and latent_dim do not apply")
        if state_rows is not None and cache_quant is not None:
            raise ValueError("a recurrent-state pool is float32: "
                             "cache_quant does not apply to it")
        if latent_dim is not None and (cache_quant is not None
                                       or state_rows is not None):
            raise ValueError("a latent pool holds one compressed row "
                             "a position in the compute dtype: neither "
                             "cache_quant nor state_rows applies to it")
        if block < 1 or block & (block - 1):
            raise ValueError(f"block={block} must be a power of two "
                             "(pages must tile the power-of-two "
                             "prompt buckets exactly)")
        if n_pages < 2:
            raise ValueError(f"n_pages={n_pages}: need at least one "
                             "usable page beyond the trash page")
        if cache_quant not in (None, "int8"):
            raise ValueError(f"cache_quant={cache_quant!r} "
                             "(None | 'int8')")
        self.n_layers = n_layers
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.n_pages = n_pages
        self.block = block
        self.cache_quant = cache_quant
        #: rows of a kv head's stored state (None: a KV-page pool)
        self.state_rows = state_rows
        #: values of a position's latent row (None: no latent pool)
        self.latent_dim = latent_dim
        shape = (n_layers, n_pages, block, n_kv_heads, 2 * head_dim)
        #: bytes of state ONE live slot's decode step reads and writes
        #: (0: the pool holds no recurrent state)
        self.state_bytes_per_slot = 0
        #: the class of the step's cache object (:meth:`rows`), chosen
        #: HERE, once, with the pool it addresses. The class says what
        #: else depends on the kind of page: whether a step walks KV
        #: pages (``walks_kv``), which of the pool's arrays are
        #: recurrent state (``state``) and, as ``chunk``, the cache
        #: object of chunk admission where the kind admits by chunks.
        self.cache = PagedKV
        #: pages of a window layer's ring (0: no window layers) and
        #: the decode slots that own one each
        self.ring = self.rings = 0
        if windowed is not None:
            self.cache = PagedWindowed
            spec, slots = windowed
            self.rings = slots
            self.ring = ring_pages(spec.window, block)
            page = (block * n_kv_heads, 2 * head_dim)
            self._pool: Tuple = (
                jnp.zeros((n_layers, n_pages, *page), jnp.dtype(dtype)),
                jnp.zeros((len(spec.layers("window")),
                           1 + slots * self.ring, *page),
                          jnp.dtype(dtype)))
        elif ssm is not None:
            self.cache = PagedHybrid
            spec, slots = ssm
            n_ssm = len(spec.layers("mamba2"))
            tail = (spec.d_conv - 1) * spec.conv_dim
            self._pool: Tuple = (
                jnp.zeros(shape, jnp.dtype(dtype)),
                jnp.zeros((n_ssm, 1 + slots, spec.d_state, spec.d_inner),
                          jnp.float32),
                jnp.zeros((n_ssm, 1 + slots, tail), jnp.dtype(dtype)))
            # the state and the tail of every Mamba layer, both ways
            self.state_bytes_per_slot = 2 * n_ssm * (
                4 * spec.d_state * spec.d_inner
                + tail * jnp.dtype(dtype).itemsize)
        elif latent_dim is not None:
            self.cache = PagedLatent
            self._pool: Tuple = (jnp.zeros(
                (n_layers, n_pages, block, latent.lanes(latent_dim)),
                jnp.dtype(dtype)),)
        elif state_rows is not None:
            self.cache = PagedState
            self._pool: Tuple = (
                jnp.zeros((n_layers, n_pages, n_kv_heads, state_rows,
                           head_dim), jnp.float32),
                jnp.zeros((n_layers, n_pages, n_kv_heads, head_dim,
                           head_dim), jnp.float32))
            # by the logical size (d (d + 1) / 2 rows of d values and
            # the normaliser's, float32, both ways)
            self.state_bytes_per_slot = (
                2 * 4 * n_layers * n_kv_heads
                * retention.logical_state_rows(head_dim) * (head_dim + 1))
        elif cache_quant == "int8":
            self._pool: Tuple = (
                jnp.zeros(shape, jnp.int8),
                jnp.zeros((n_layers, n_pages, n_kv_heads, 2, block),
                          jnp.float32))
        else:
            self._pool = (jnp.zeros(shape, jnp.dtype(dtype)),)
        # host bookkeeping: LIFO free list (hot pages stay hot), the
        # page -> refcount map, and the per-owner page lists the
        # invariant checks cross-foot against the refcounts
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self._refs: Dict[int, int] = {}
        self._pages_of: Dict[int, List[int]] = {}
        # content-addressed page-chain index: (kind, n_tokens,
        # token_bytes) -> page list. "pages" entries cover full pages
        # of a prompt prefix; "tail" entries cover a whole prompt
        # including its partial last page (adopters must CoW it before
        # recomputing the final position). Entries die with any member
        # page (reverse map below).
        self._chains: Dict[tuple, List[int]] = {}
        self._page_keys: Dict[int, set] = {}
        # per-tenant reserved-page accounting (owners carry .tenant —
        # the gateway's TokenStream does); label cardinality capped
        # like the gateway's request counter: tenant names are
        # caller-controlled and a gauge child lives forever. Shared
        # pages bill EVERY tenant referencing them (reservation
        # semantics: each owner's whole-life claim).
        self._tenant_of: Dict[int, str] = {}
        self._tenant_pages: Dict[str, int] = {}
        self._tenant_labels: set = set()
        self.max_tenant_labels = 64
        _metrics.SERVING_STATE_POOL.set(self.state_pool_bytes())
        self._gauge()

    # -- device pool -----------------------------------------------------
    @property
    def pool(self) -> Tuple:
        """The layer-stacked device arrays the jitted step reads and
        rewrites: ``(codes,)`` or ``(codes, scales)`` of KV pages,
        ``(S, Z)`` of a recurrent-state pool, ``(rows,)`` of a latent
        pool, ``(kv, H, tail)`` of a hybrid's, ``(kv_full, kv_window)``
        of a windowed decoder's."""
        return self._pool

    @pool.setter
    def pool(self, new: Tuple) -> None:
        self._pool = tuple(new)

    def pool_bytes(self) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self._pool)

    def state_pool_bytes(self) -> int:
        """Bytes of the recurrent state the pool holds as stored,
        trash page included (0 for KV and latent pages alone)."""
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in self._pool[self.cache.state])

    @property
    def walks_kv(self) -> bool:
        """Whether a decode step's attention walks KV pages."""
        return self.cache.walks_kv

    # -- inside a traced program, over ITS pool -------------------------
    def rows(self, dims, pool, pt, pos, act):
        """The cache object of R rows a slot: ``pt`` [S, MP] i32 the
        slots' page-table rows, ``pos`` [S, R] i32 the rows'
        positions, ``act`` bool broadcastable to [S, R] (False rows
        write nothing a live sequence reads). Its ``pool`` is the pool
        after the rows."""
        return self.cache(dims, pool, pt, pos, act)

    def prompt_pages(self, slot: int, pages: List[int], tb: int,
                     t0: int):
        """What :meth:`write_prompt` takes as ``page_ids`` for the
        sequence in ``slot`` with ``pages`` off the free list, a
        bucket of ``tb`` rows and ``t0`` prompt tokens: the first ``tb
        / block`` pages; for a windowed pool beside them the bucket's
        pages a window layer keeps (``src [ring]``: the last ``ring``
        up to the one that holds position ``t0 - 1``) and the slot's
        ring pages they go to (``dst [ring]``; the trash page where
        the prompt has fewer)."""
        ids = np.asarray(pages[:tb // self.block], np.int32)
        if not self.ring:
            return jnp.asarray(ids)
        src = (t0 - 1) // self.block - self.ring + 1 + np.arange(
            self.ring, dtype=np.int32)
        dst = np.where(src >= 0, 1 + slot * self.ring + src % self.ring,
                       0).astype(np.int32)
        return (jnp.asarray(ids), jnp.asarray(np.maximum(src, 0)),
                jnp.asarray(dst))

    def prompt_pages_shapes(self, tb: int):
        """:meth:`prompt_pages` as shapes, for lowering."""
        import jax
        sds = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32)
        ids = sds(tb // self.block)
        return (ids, sds(self.ring), sds(self.ring)) if self.ring else ids

    @staticmethod
    def write_prompt(pool, page_ids, layers, spec=None):
        """``pool`` with one sequence's bucket prefill written as whole
        pages: ``layers`` each layer's ``(k, v) [1, Tb, Hkv, D]``
        (``decoder_infer.causal_prefill``'s ``keep``), ``page_ids`` the
        sequence's first ``Tb / block`` pages in position order. The
        pool's own layout, so nothing is transposed on the way, and
        all layers go in one scatter. A latent pool takes each layer's
        latent rows ``[1, Tb, latent_dim]``
        (``decoder_infer.latent_prefill``'s ``keep``). A windowed pool
        (``spec`` its ``WindowSpec``, ``page_ids`` as
        :meth:`prompt_pages` gives them) takes every full layer's
        pages and, of a window layer's, the last ``ring``."""
        if spec is not None:
            full, window = pool
            ids, src, dst = page_ids
            kv = jnp.stack([jnp.concatenate([k[0], v[0]], axis=-1)
                            for k, v in layers])    # [L, Tb, Hkv, 2D]
            tb = kv.shape[1]
            rows = full.shape[2]
            kv = kv.reshape(kv.shape[0], tb * kv.shape[2] // rows, rows,
                            kv.shape[3]).astype(full.dtype)
            at = lambda kind: jnp.asarray(spec.layers(kind), jnp.int32)
            return (full.at[:, ids].set(kv[at("full")]),
                    window.at[:, dst].set(kv[at("window")][:, src]))
        if pool[0].ndim == 4:
            (rows,) = pool
            lat = jnp.stack([r[0] for r in layers])     # [L, Tb, W]
            n_l, tb, width = lat.shape
            block, stored = rows.shape[2:]
            lat = jnp.pad(lat, ((0, 0), (0, 0), (0, stored - width)))
            return (rows.at[:, page_ids].set(lat.reshape(
                n_l, tb // block, block, stored).astype(rows.dtype)),)
        kv = jnp.stack([jnp.concatenate([k[0], v[0]], axis=-1)
                        for k, v in layers])    # [L, Tb, Hkv, 2D]
        n_l, tb, n_kv, d2 = kv.shape
        block = pool[0].shape[2]
        paged = (n_l, tb // block, block, n_kv)
        if len(pool) == 2:
            codes, scales = pool
            w8, s = di.quant_kv(kv.reshape(n_l, tb, n_kv, 2, d2 // 2), 4)
            return (codes.at[:, page_ids].set(w8.reshape(*paged, d2)),
                    scales.at[:, page_ids].set(
                        s.reshape(*paged, 2).transpose(0, 1, 3, 4, 2)))
        (kvpool,) = pool
        return (kvpool.at[:, page_ids].set(
            kv.reshape(*paged, d2).astype(kvpool.dtype)),)

    @staticmethod
    def copy_page(pool, src, dst):
        """``pool`` with page ``src`` copied over page ``dst`` (all
        layers, every array): the copy-on-write primitive."""
        return tuple(a.at[:, dst].set(a[:, src]) for a in pool)

    # -- allocation ------------------------------------------------------
    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` cache positions (a
        recurrent state takes one page whatever the length)."""
        if self.state_rows is not None:
            return 1
        return -(-int(n_tokens) // self.block)

    def alloc(self, n: int, owner) -> Optional[List[int]]:
        """Take ``n`` exclusive pages (refcount 1) for ``owner`` (any
        hashable-by-id object — the gateway uses the request stream).
        Returns the page ids in position order, or None when the pool
        can't satisfy the request — admission control's signal to keep
        the request queued rather than wedge a slot mid-flight."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self._pages_of.setdefault(id(owner), []).extend(pages)
        self._bill_tenant(owner, n)
        self._gauge()
        return pages

    def adopt(self, pages: List[int], owner) -> None:
        """Reference already-live pages for ``owner`` (prefix sharing:
        the admission that matched a page chain rides the donor's
        physical pages). Refcounts bump by one per page; the pages
        come back via the same :meth:`release` as allocated ones."""
        mine = self._pages_of.setdefault(id(owner), [])
        for p in pages:
            if p == 0:
                raise PageTableError("cannot adopt trash page 0")
            rc = self._refs.get(p)
            if rc is None:
                raise PageTableError(
                    f"cannot adopt page {p}: not live")
            if p in mine:
                raise PageTableError(
                    f"owner already references page {p}")
            self._refs[p] = rc + 1
            mine.append(p)
        self._bill_tenant(owner, len(pages))
        self._gauge()

    def drop_ref(self, owner, page: int) -> bool:
        """Drop ``owner``'s reference on one page (the CoW path:
        after copying a shared page the writer releases the original).
        Returns True when this was the last reference and the page
        went back to the free list."""
        mine = self._pages_of.get(id(owner), [])
        if page not in mine:
            raise PageTableError(
                f"owner does not reference page {page}")
        mine.remove(page)
        self._bill_tenant(owner, -1)
        freed = self._decref(page)
        self._gauge()
        return freed

    def cow(self, owner, old_page: int) -> int:
        """Copy-on-write bookkeeping: take a fresh exclusive page for
        ``owner`` and drop its reference on ``old_page`` (which stays
        live for its other holders). The caller performs the device
        page copy BEFORE redirecting writes. Raises when the free list
        is empty — admissions that adopt a writable (tail) page
        reserve the CoW target up front so this never fires
        mid-flight."""
        if not self._free:
            raise PageTableError(
                "copy-on-write needs a free page but the pool is "
                "empty — tail-sharing admissions must reserve one")
        new = self.alloc(1, owner)[0]
        self.drop_ref(owner, old_page)
        return new

    def release(self, owner) -> int:
        """Drop every reference ``owner`` holds; pages whose LAST
        reference this was go back to the free list. Returns the
        number of pages actually freed (== pages held, when none were
        shared)."""
        pages = self._pages_of.pop(id(owner), [])
        freed = 0
        for p in pages:
            freed += self._decref(p)
        tenant = self._tenant_of.pop(id(owner), None)
        if tenant is not None and pages:
            self._tenant_pages[tenant] = max(
                0, self._tenant_pages.get(tenant, 0) - len(pages))
        self._gauge()
        return freed

    def owned(self, owner) -> List[int]:
        return list(self._pages_of.get(id(owner), []))

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def shared_pages(self) -> int:
        """Pages currently referenced by more than one live sequence
        (the ``dl4j_tpu_serving_prefix_shared_pages`` gauge)."""
        return sum(1 for rc in self._refs.values() if rc > 1)

    def _decref(self, p: int) -> bool:
        rc = self._refs.get(p)
        if rc is None:
            raise PageTableError(f"double free of page {p}")
        if rc > 1:
            self._refs[p] = rc - 1
            return False
        del self._refs[p]
        self._free.append(p)
        # a freed page invalidates every chain entry it belonged to
        for key in self._page_keys.pop(p, set()):
            entry = self._chains.pop(key, None)
            if entry:
                for q in entry:
                    ks = self._page_keys.get(q)
                    if ks is not None:
                        ks.discard(key)
        return True

    # -- content-addressed page-chain index ------------------------------
    def register_chain(self, tokens: np.ndarray,
                       pages: List[int]) -> None:
        """Index ``tokens``'s page chain so later admissions with a
        shared prefix can ride these pages. One entry per full-page
        prefix (key: the token bytes the pages cover) plus one "tail"
        entry for the whole prompt (its last page may be partial —
        adopters CoW it). First registrant wins on key collisions."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        t0 = int(tokens.shape[0])
        for i in range(1, t0 // self.block + 1):
            key = ("pages", i * self.block,
                   tokens[:i * self.block].tobytes())
            self._index(key, pages[:i])
        npg = self.pages_for(t0)
        if len(pages) >= npg:
            self._index(("tail", t0, tokens.tobytes()), pages[:npg])

    def _index(self, key: tuple, pages: List[int]) -> None:
        if key in self._chains or not pages:
            return
        if any(self._refs.get(p) is None or p == 0 for p in pages):
            return      # never index dead or trash pages
        self._chains[key] = list(pages)
        for p in pages:
            self._page_keys.setdefault(p, set()).add(key)

    def match_prefix(self, tokens: np.ndarray
                     ) -> Optional[Tuple[int, List[int], bool]]:
        """Longest indexed prefix of ``tokens``: returns
        ``(shared_len, pages, tail)`` or None. ``tail=True`` means the
        whole prompt matched — the adopter shares every page but must
        CoW the last one and recompute position ``t0-1`` (shared
        coverage is capped at ``t0-1`` so admission always produces
        the first generated token from its own logits). ``tail=False``
        shares full pages only (``shared_len`` a multiple of
        ``block``, at most ``t0-1``) — shared pages are then never
        written by the adopter."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        t0 = int(tokens.shape[0])
        entry = self._chains.get(("tail", t0, tokens.tobytes()))
        if entry is not None:
            return t0 - 1, list(entry), True
        for i in range((t0 - 1) // self.block, 0, -1):
            entry = self._chains.get(
                ("pages", i * self.block,
                 tokens[:i * self.block].tobytes()))
            if entry is not None:
                return i * self.block, list(entry), False
        return None

    def reserved_by_tenant(self) -> Dict[str, int]:
        """Live reserved-page counts per tenant label (the gauge's
        source — whole-life reservations, not just written pages)."""
        return {t: n for t, n in self._tenant_pages.items() if n}

    def _bill_tenant(self, owner, n: int) -> None:
        tenant = self._tenant_of.get(id(owner))
        if tenant is None:
            tenant = self._tenant_label(owner)
            self._tenant_of[id(owner)] = tenant
        self._tenant_pages[tenant] = max(
            0, self._tenant_pages.get(tenant, 0) + n)

    def _tenant_label(self, owner) -> str:
        tenant = str(getattr(owner, "tenant", "") or "unknown")
        if tenant in self._tenant_labels or \
                len(self._tenant_labels) < self.max_tenant_labels:
            self._tenant_labels.add(tenant)
            return tenant
        return "other"

    def _gauge(self) -> None:
        _metrics.SERVING_PAGES_FREE.set(len(self._free))
        usable = self.n_pages - 1
        _metrics.SERVING_KV_OCCUPANCY.set(
            (usable - len(self._free)) / usable)
        _metrics.SERVING_PREFIX_SHARED.set(self.shared_pages())
        if self.ring:
            # the slot is the reservation: a sequence's window pages
            # are counted from its full pages, not held anywhere
            held = _metrics.SERVING_KV_PAGES_HELD
            held.labels(kind="full").set(usable - len(self._free))
            held.labels(kind="window").set(sum(
                min(len(p), self.ring) for p in self._pages_of.values()))
        for tenant, n in self._tenant_pages.items():
            _metrics.SERVING_KV_RESERVED.labels(tenant=tenant).set(n)

    # -- invariants (tests/test_serving.py churn fence) ------------------
    def check_invariants(self) -> None:
        """Refcount conservation (per page, the number of live table
        references equals its refcount — trash page exempt because it
        is never allocated), no page both free and referenced, trash
        page out of circulation, no double free, and allocation
        conservation: free + referenced == n_pages - 1. Raises
        :class:`PageTableError` on any breach."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise PageTableError("duplicate pages on the free list")
        counts: Dict[int, int] = {}
        for pages in self._pages_of.values():
            for p in pages:
                counts[p] = counts.get(p, 0) + 1
        if 0 in counts or 0 in free or 0 in self._refs:
            raise PageTableError("trash page 0 entered circulation")
        for p in set(counts) | set(self._refs):
            occ, rc = counts.get(p, 0), self._refs.get(p, 0)
            if occ > rc:
                raise PageTableError(
                    f"page {p}: {occ} table references != refcount "
                    f"{rc} (two live sequences sharing a page must "
                    "both hold a ref)")
            if occ < rc:
                raise PageTableError(
                    f"page {p}: refcount {rc} leaks past its {occ} "
                    "live table references")
        if free & set(self._refs):
            raise PageTableError(
                f"pages both free and referenced: "
                f"{sorted(free & set(self._refs))}")
        if len(free) + len(self._refs) != self.n_pages - 1:
            raise PageTableError(
                f"page leak: {len(free)} free + {len(self._refs)} "
                f"referenced != {self.n_pages - 1} usable")
        for key, pages in self._chains.items():
            for p in pages:
                if p not in self._refs:
                    raise PageTableError(
                        f"chain entry {key[:2]} references freed "
                        f"page {p}")
        if self.ring and self._pool[1].shape[1] != \
                1 + self.rings * self.ring:
            # what keeps a sequence to ``ring`` pages of a window
            # layer is the pool's shape: a slot's ring IS its pages
            raise PageTableError(
                f"the window pool has {self._pool[1].shape[1]} pages, "
                f"not the trash page and {self.rings} rings of "
                f"{self.ring}")


# -- the pool's cache objects (nn/decoder_infer.py's contract) ---------------

class _Rows:
    """What :class:`KVPager` asks of the step's cache class beside the
    ``decoder_infer`` contract, for all that depends on the kind of
    page it addresses."""
    #: whether a decode step's attention walks KV pages
    walks_kv = False
    #: which of the pool's arrays are recurrent state
    state = slice(0, 0)
    #: the cache class of chunk admission, ``chunk(dims, pool,
    #: carried, where, start, valid)`` (None: the kind admits a prompt
    #: as ONE bucket)
    chunk = None

    def __init__(self, dims, pool, pt, pos, act):
        self.dims = dims
        self.pool = pool
        self.pt = pt
        self.pos = pos
        self.act = act


class PagedKV(_Rows):
    """R positions a slot against the KV pool: row r's KV goes to page
    ``pt[s, pos // block]`` at offset ``pos % block``, and the
    attention reads the slot's pages through its page-table row.
    ``decoder_infer.DenseKV``'s arithmetic value for value (the
    token-identity fences of ``tests/test_serving.py``); only the
    addressing differs. Every matmul runs on the flattened [S*R, F]
    rows, so a row's arithmetic is the same whatever R is (the
    spec-decode fence leans on that). A position past the slot's page
    table is clamped EXPLICITLY and routed to the trash page: JAX
    gathers clamp silently, and junk must never land in a live page.
    Over a FOLDED pool ``[L, P, block * Hkv, 2D]`` (a windowed
    decoder's) R is 1; ``layers`` then names the model's layer of each
    layer of this pool (a rotation may differ by layer)."""
    walks_kv = True

    def __init__(self, dims, pool, pt, pos, act, layers=None):
        super().__init__(dims, pool, pt, pos, act)
        self.layers = layers
        #: positions a query sees (None: all)
        self.window = None

    def pages(self, block: int):
        """Where each row's KV goes, ``(page [S, R], in bounds [S,
        R])``: the slot's page-table entry of the row's position."""
        pt, pos = self.pt, self.pos
        inb = self.act & (pos < pt.shape[1] * block)
        pidx = jnp.minimum(pos // block, pt.shape[1] - 1)
        return (jnp.where(inb, jnp.take_along_axis(pt, pidx, axis=1), 0),
                inb)

    def read(self, q, pool, li, inb, n_kv):
        """The decode step's read: each slot's pages in place."""
        return paged_decode_attention(
            q, pool, li, self.pt,
            jnp.where(inb[:, 0], self.pos[:, 0] + 1, 0),
            window=self.window, n_kv=n_kv)

    def attend(self, li, mha, h):
        dims, pool, pt, pos = self.dims, self.pool, self.pt, self.pos
        S, R = pos.shape
        pflat = pos.reshape(S * R)
        theta = (dims.rope_theta if self.layers is None
                 else di.layer_theta(dims, self.layers[li]))
        q, k, v = di.qkv(mha, h, dims, lambda z: di.rotary_rows(
            z, theta, pflat))
        n_kv, hd = k.shape[1:]
        if pool[0].ndim == 4:
            return self._attend_folded(li, q, k, v)
        block = pool[0].shape[2]
        q = q.reshape(S, R, dims.n_heads, hd)
        kv = jnp.concatenate([k.reshape(S, R, n_kv, hd),
                              v.reshape(S, R, n_kv, hd)],
                             axis=3)                    # [S, R, Kv, 2D]
        pids, inb = self.pages(block)
        offs = pos % block
        if len(pool) == 2:
            codes, scales = pool
            q8, s_new = di.quant_kv(kv.reshape(S, R, n_kv, 2, hd), 4)
            pool = (codes.at[li, pids, offs].set(
                        q8.reshape(S, R, n_kv, 2 * hd)),
                    scales.at[li, pids, :, :, offs].set(s_new))
        else:
            (kvpool,) = pool
            pool = (kvpool.at[li, pids, offs].set(
                kv.astype(kvpool.dtype)),)
        self.pool = pool
        # the scatter above runs before the read, so a row attends its
        # own key and every earlier row's; later rows' keys (and any
        # stale speculative garbage past the accepted length) sit
        # strictly beyond pos and stay at exact-zero softmax weight
        if R == 1:
            # THE decode step: the kernel reads each slot's pages in
            # place, up to its length (a routed-to-trash row is an
            # inactive slot: it walks no page and returns zeros)
            a = paged_decode_attention(
                q[:, 0], pool, li, pt,
                jnp.where(inb[:, 0], pos[:, 0] + 1, 0))
        else:
            a = _reference_paged_attention(q, pool, li, pt, pos)
        return a.reshape(S * R, -1)

    def _attend_folded(self, li, q, k, v):
        """One position a slot against a folded pool: the position's
        ``[Hkv, 2D]`` goes to rows ``offset * Hkv ..`` of its page as
        ONE window of the scatter, in place."""
        (kvpool,) = self.pool
        S, n_kv, hd = k.shape
        block = kvpool.shape[2] // n_kv
        pids, inb = self.pages(block)
        kv = jnp.concatenate([k, v], axis=2).astype(kvpool.dtype)
        at = jnp.stack([jnp.full((S,), li, jnp.int32), pids[:, 0],
                        (self.pos[:, 0] % block) * n_kv], axis=1)
        kvpool = lax.scatter(
            kvpool, at, kv, lax.ScatterDimensionNumbers(
                update_window_dims=(1, 2), inserted_window_dims=(0, 1),
                scatter_dims_to_operand_dims=(0, 1, 2)))
        self.pool = (kvpool,)
        return self.read(q, self.pool, li, inb, n_kv).reshape(S, -1)


class PagedWindowKV(PagedKV):
    """One position a slot against a windowed decoder's RING pool
    ``[L_window, 1 + slots * ring, block * Hkv, 2D]``: slot ``s`` owns
    pages ``1 + s * ring ..``, position ``t`` lies in its page ``(t //
    block) % ring``. The slot's ring is its row of the page table,
    ``ring`` entries long, and ``paged_decode_attention(window=)``
    reads such a row modulo its length: the walk starts at the page
    of the window's first position, masks that page's head and the
    last one's stale tail, and takes ``ring`` pages at most."""

    def __init__(self, dims, pool, pos, act, layers):
        ring = (pool[0].shape[1] - 1) // pos.shape[0]
        base = 1 + ring * jnp.arange(pos.shape[0], dtype=jnp.int32)
        super().__init__(dims, pool, base[:, None] + jnp.arange(
            ring, dtype=jnp.int32)[None, :], pos, act, layers)
        self.ring = ring
        self.window = dims.windowed.window

    def pages(self, block: int):
        inb = jnp.broadcast_to(self.act, self.pos.shape)
        at = (self.pos // block) % self.ring
        return (jnp.where(inb, jnp.take_along_axis(self.pt, at, axis=1),
                          0), inb)


class _Scoped:
    """A cache object whose ``attend`` runs under a devtime scope."""

    def __init__(self, inner, name: str):
        self.inner = inner
        self.name = name

    def attend(self, li, mha, h):
        with devtime.scope(self.name):
            return self.inner.attend(li, mha, h)

    @property
    def pool(self):
        return self.inner.pool


class PagedWindowed(di.ByKind):
    """The step's cache object over a windowed decoder's pool
    ``(kv_full, kv_window)``: a full layer's rows go to
    :class:`PagedKV` over the pages of the slot's page-table row, a
    window layer's to :class:`PagedWindowKV` over the slot's ring,
    each under the layer's index among ITS kind and under a scope of
    its kind (``attn.full``, ``attn.window``: the two page walks'
    device times are told apart by it)."""
    walks_kv = True
    state = slice(0, 0)
    chunk = None

    def __init__(self, dims, pool, pt, pos, act):
        spec = dims.windowed
        if pos.shape[1] != 1:
            raise ValueError("a windowed pool serves one position a "
                             "slot (no multi-row program)")
        super().__init__(
            spec,
            full=_Scoped(PagedKV(dims, pool[:1], pt, pos, act,
                                 spec.layers("full")), "attn.full"),
            window=_Scoped(PagedWindowKV(dims, pool[1:], pos, act,
                                         spec.layers("window")),
                           "attn.window"))


class PagedLatent(_Rows):
    """One position a slot against the latent pool: the row's latent
    ``[c_kv (normed) | k_rope (rotated)]`` goes to page ``pt[s, pos //
    block]`` at offset ``pos % block`` (an inactive slot's, and a
    position past the slot's page table, to the trash page), and the
    ABSORBED form reads the slot's pages as they are stored
    (``ops.latent_decode_attention``; ``ops/latent.py`` has the
    algebra). (R is 1: the scheduler refuses the multi-row programs
    for a latent model at construction.)"""

    def attend(self, li, mha, h):
        dims, pt = self.dims, self.pt
        spec = dims.latent
        (rows,) = self.pool
        S = h.shape[0]
        block = rows.shape[2]
        pos = self.pos.reshape(S)
        q_nope, q_rope, row = latent.project(
            mha, h, spec, dims.n_heads, dims.rope_theta, pos)
        inb = jnp.broadcast_to(self.act, (S, 1))[:, 0] & (
            pos < pt.shape[1] * block)
        pidx = jnp.minimum(pos // block, pt.shape[1] - 1)
        pids = jnp.where(inb, jnp.take_along_axis(
            pt, pidx[:, None], axis=1)[:, 0], 0)
        row = jnp.pad(row, ((0, 0), (0, rows.shape[3] - row.shape[1])))
        rows = rows.at[li, pids, pos % block].set(row.astype(rows.dtype))
        self.pool = (rows,)
        o = latent_decode_attention(
            latent.absorb(mha, q_nope, q_rope, spec), rows, li, pt,
            jnp.where(inb, pos + 1, 0), latent.softmax_scale(spec),
            spec.kv_rank)
        return latent.unabsorb(mha, o, spec)


class StateChunk(di.RetentionRows):
    """One chunk of a retention prompt (batch 1) against the
    sequence's state page: a layer reads the state the chunk before
    left there (an empty one when ``start`` is 0: a page comes off the
    free list as its last owner left it) and writes its own back; what
    its queries need of the chunks before they read from ``history``
    (``ops.retention.zero_history``, one layer a row), which gets this
    chunk's rows added: it is what a prompt's chunks hand on beside
    the pool (``carried``). ``pool`` and ``carried`` are both after
    the chunk."""

    def __init__(self, dims, pool, carried, page, start, valid):
        super().__init__(dims, start, valid)
        self.pool = pool
        self.carried = carried
        self.page = page

    @staticmethod
    def where(slot: int, pages: List[int], page_row):
        """Where the sequence in ``slot`` keeps its state: the one
        page it was allocated."""
        return jnp.asarray(pages[0], jnp.int32)

    def state(self, li):
        return tuple(jnp.where(self.start > 0, a[li, self.page], 0.0)[None]
                     for a in self.pool)

    def history(self, li):
        return tuple(a[li, None] for a in self.carried)

    def keep(self, li, state, hist):
        self.pool = tuple(a.at[li, self.page].set(new[0])
                          for a, new in zip(self.pool, state))
        self.carried = tuple(a.at[li].set(new[0])
                             for a, new in zip(self.carried, hist))


class PagedState(_Rows):
    """One position a slot against the state pool: the slot's ONE
    state page (``pt``'s only column) is updated in place and read
    (``ops.retention_decode``); an inactive slot's page is neither.
    (R is 1: the scheduler refuses the multi-row programs for a
    retention model at construction.)"""
    state = slice(None)
    chunk = StateChunk

    def attend(self, li, mha, h):
        dims = self.dims
        S = h.shape[0]
        q, k, v, log_g = retention.project(
            mha, h, dims.n_heads, dims.n_kv_heads,
            lambda z: di.rotary_rows(z, dims.rope_theta,
                                     self.pos.reshape(S)), RMSNORM_EPS)
        a, self.pool = retention_decode(
            q, k, v, jnp.exp(log_g), self.pool, li, self.pt[:, 0],
            jnp.broadcast_to(self.act, (S, 1))[:, 0])
        return a.reshape(S, -1)


class PagedSSM:
    """One position a slot against a hybrid's state pool ``(H, tail)``:
    slot ``s`` owns state page ``s + 1``; its state is updated in
    place and read (``ops.ssm_decode``), its convolution's tail moved
    on by one row. An inactive slot's state page is neither read nor
    written, and its tail goes to the trash page. ``li`` counts the
    Mamba layers (``decoder_infer.ByKind``)."""

    def __init__(self, dims, pool, act):
        self.dims = dims
        self.pool = pool
        self.act = act

    def attend(self, li, mha, h):
        spec = self.dims.hybrid
        state, tails = self.pool
        S = h.shape[0]
        act = jnp.broadcast_to(self.act, (S, 1))[:, 0]
        pages = jnp.arange(1, S + 1, dtype=jnp.int32)
        pids = jnp.where(act, pages, 0)

        def update(x, b, c, delta, a_neg, d_skip):  # -> (y, the pool)
            return ssm_decode(x, b, c, delta, a_neg, d_skip, state, li,
                              pages, act)

        a, state, tail = ssm.mixer_rows(
            mha, h, spec, None,
            tails[li, pids].reshape(S, spec.d_conv - 1, -1), update)
        self.pool = (state, tails.at[li, pids].set(tail.reshape(S, -1)))
        return a


class SSMChunk(di.SSMRows):
    """One chunk of a prompt (batch 1) against the sequence's state
    page of a hybrid's pool ``(H, tail)``: a Mamba layer reads what
    the chunk before left there (an empty state and a zero tail when
    ``start`` is 0: a page comes to a sequence as its last owner left
    it) and writes its own back."""

    def __init__(self, dims, pool, page, start, valid):
        super().__init__(dims, valid)
        self.pool = pool
        self.page = page
        self.start = start

    def state(self, li):
        state, tails = self.pool
        k1 = self.dims.hybrid.d_conv - 1
        return (jnp.where(self.start > 0, state[li, self.page], 0.0)[None],
                jnp.where(self.start > 0, tails[li, self.page],
                          0).reshape(1, k1, -1))

    def keep(self, li, state, tail):
        pool, tails = self.pool
        self.pool = (pool.at[li, self.page].set(state[0]),
                     tails.at[li, self.page].set(tail.reshape(-1)))


class _OneSlot:
    """A chunk's rows ``[1, C, F]`` as the flat rows of ONE slot that
    :class:`PagedKV` takes, and back."""

    def __init__(self, inner):
        self.inner = inner

    def attend(self, li, mha, h):
        return self.inner.attend(li, mha, h[0])[None]

    @property
    def pool(self):
        return self.inner.pool


class HybridChunk(di.ByKind):
    """One chunk of a hybrid's prompt (rows ``[1, C]`` at positions
    ``start ..``): its Mamba layers against the sequence's state page
    (:class:`SSMChunk`), its attention layers as C rows of ONE slot
    against the KV pages of the sequence's page-table row, which the
    chunks before have written (:class:`PagedKV`). ``where`` is both:
    ``(state page, page-table row [MP])``. The pools hold all a later
    chunk reads, so the chunks hand nothing on beside them
    (``carried`` is empty)."""

    def __init__(self, dims, pool, carried, where, start, valid):
        page, pt_row = where
        pos = start + jnp.arange(valid.shape[1], dtype=jnp.int32)[None]
        super().__init__(        # in the pool tuple's order
            dims.hybrid,
            softmax=_OneSlot(PagedKV(dims, pool[:1], pt_row[None], pos,
                                     valid)),
            mamba2=SSMChunk(dims, pool[1:], page, start, valid))
        self.carried = carried

    @staticmethod
    def where(slot: int, pages: List[int], page_row):
        """Where the sequence in ``slot`` keeps its state: state page
        ``slot + 1`` (the slot IS the reservation) and the KV pages of
        its page-table row (a copy: the host's mirror is written while
        programs run)."""
        return (jnp.asarray(slot + 1, jnp.int32),
                jnp.asarray(np.array(page_row, np.int32)))


class PagedHybrid(di.ByKind):
    """The step's cache object over a hybrid's pool ``(kv, H, tail)``:
    an attention layer's rows go to :class:`PagedKV` over the KV
    pages, a Mamba layer's to :class:`PagedSSM` over the state pages,
    each under the layer's index among ITS kind."""
    walks_kv = True
    state = slice(1, None)
    chunk = HybridChunk

    def __init__(self, dims, pool, pt, pos, act):
        super().__init__(
            dims.hybrid, softmax=PagedKV(dims, pool[:1], pt, pos, act),
            mamba2=PagedSSM(dims, pool[1:], act))

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
  (:class:`PagedWindowed`). dtype is ``int8`` under
  ``cache_quant="int8"`` (codes from ``nn.decoder_infer.quant_kv``,
  the same quantiser the dense path uses — the pager-correctness
  fence demands token identity), else the model's compute dtype.
- ``scales`` ``[L, P, Hkv, 2, block]`` f32 — per-(page, head, k/v
  half, position) dequant scales; present only under int8. Positions
  stay minor here: a minor dimension of 2 would pad every pair of
  scales to a 128-lane row.

Those are KV pages (:class:`PagedKV`). The same pager holds the other
kinds of page a decoder keeps a sequence's context in, each kind's
layout with its class: ONE fixed-size recurrent-state page a sequence
(:class:`PagedState`), one compressed latent row a position
(:class:`PagedLatent`), a hybrid's KV pages beside its decode slot's
state page (:class:`PagedHybrid`), a windowed decoder's full pages
beside its decode slot's ring (:class:`PagedWindowed`: the two kinds
of softmax layer may differ in their query heads and in their rotary
rule, never in their KV heads or a head's width, so a page of either
kind is one shape). Allocation,
reservation, the free list and every invariant below are the same
for all; what belongs to a decode slot has no free list, no refcount
and no invariant of its own: the slot IS the reservation, what can
leak is a page off the free list, and ``free_pages`` counts those.

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

The layouts are known HERE and to the kernels that read them
(``ops/pallas_kernels.py``), nowhere else, and so is everything that
depends on the KIND of page: the class of the step's cache object
(``nn/decoder_infer.py``'s contract, ``attend(li, mha, h)``) IS the
kind, :attr:`KVPager.cache` (the two of a decoder whose layers differ
go PER LAYER by the layer's kind, ``decoder_infer.ByKind``), picked
from the model in ONE place (:meth:`KVPager.kind_of`). The class says
all that differs by kind (:class:`_Rows` is the contract): the
options it refuses, its pool's arrays and sizes, how a prompt is
admitted (ONE bucket, or chunks of how many rows: ``chunk`` is then
the cache class of chunk admission, which says where it finds a
sequence's state, ``where``, and what a prompt's chunks hand on,
``carried``), what a bucket prefill keeps and how it is written as
pages, what a decode step reads (the step record's counts) and the
guards that hold for it alone. The scheduler is the loop over *a*
pager and asks it.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.nn.layers.attention import causal_pairs
from deeplearning4j_tpu.nn.layers.core import RMSNORM_EPS
from deeplearning4j_tpu.obs import devtime
from deeplearning4j_tpu.obs import metrics as _metrics
from deeplearning4j_tpu.ops import latent, retention, ssm
from deeplearning4j_tpu.ops.pallas_kernels import (
    _reference_paged_attention, latent_chunk_pages,
    latent_decode_attention, paged_decode_attention, retention_decode,
    ssm_decode)

#: rows of a retention model's one prefill program (clamped to the
#: gateway's ``max_context``): at 512 rows the weights' matmuls are
#: bound by the MXU, not by reading the weights once a call
PREFILL_CHUNK = 512


class PageTableError(RuntimeError):
    """A pager invariant broke (page referenced without a matching
    refcount, free-list leak, double free) — raised by
    :meth:`KVPager.check_invariants`, the churn tests' fence."""


def ring_pages(window: int, block: int) -> int:
    """Pages of a window layer's ring: the ``window`` keys a query
    sees lie in at most ``ceil(window / block) + 1`` pages."""
    return -(-window // block) + 1


class KVPager:
    """Fixed pool of refcounted pages with free-list allocation.

    ``n_pages`` counts the trash page: usable capacity is
    ``n_pages - 1`` pages of ``block`` tokens each. The pool holds ONE
    kind of page, which the arguments name: none of them KV pages
    (:class:`PagedKV`; int8 under ``cache_quant``), ``state_rows``
    :class:`PagedState`, ``latent_dim`` :class:`PagedLatent`, ``ssm``
    (``(an ops.ssm.HybridSpec, decode slots)``) :class:`PagedHybrid`
    and ``windowed`` (``(a decoder_infer.WindowSpec, decode slots)``)
    :class:`PagedWindowed`; ``n_layers`` counts the layers whose pages
    come off the free list (a hybrid's attention layers, a windowed
    decoder's FULL layers). The kind is the class :attr:`cache`, and
    it allocates.
    """

    def __init__(self, *, n_layers: int, n_kv_heads: int, head_dim: int,
                 n_pages: int, block: int, cache_quant: Optional[str],
                 dtype: str = "float32",
                 state_rows: Optional[int] = None,
                 latent_dim: Optional[int] = None,
                 ssm: Optional[Tuple] = None,
                 windowed: Optional[Tuple] = None):
        given = {PagedWindowed: windowed, PagedHybrid: ssm,
                 PagedLatent: latent_dim, PagedState: state_rows}
        named = [k for k, v in given.items() if v is not None]
        kind = named[0] if named else PagedKV
        if len(named) > 1 or named and cache_quant is not None:
            # the arguments name ONE kind, and int8 is PagedKV's alone
            raise ValueError(kind.alone)
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
        #: the KIND of page, named HERE, once: the class of the step's
        #: cache object (:meth:`rows`), which says all that depends on it
        self.cache = kind
        #: bytes of state ONE live slot's decode step reads and writes
        #: (0: the pool holds no recurrent state)
        self.state_bytes_per_slot = 0
        #: pages of a window layer's ring (0: no window layers) and
        #: the decode slots that own one each
        self.ring = self.rings = 0
        self._pool: Tuple = kind.alloc(self, given.get(kind),
                                       jnp.dtype(dtype))
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

    @staticmethod
    def kind_of(model, slots: int = 0):
        """``(kind, named)``: the kind of page ``model``'s decoder
        keeps a sequence's context in, and the constructor's arguments
        that name it for ``slots`` decode slots (with ``n_layers``
        where it is not the model's). The ONE place that asks the model."""
        spec = getattr(model, "windowed", None)
        if spec is not None:
            return PagedWindowed, {"n_layers": len(spec.layers("full")),
                                   "windowed": (spec, slots)}
        spec = getattr(model, "hybrid", None)
        if spec is not None:
            return PagedHybrid, {"n_layers": len(spec.layers("softmax")),
                                 "ssm": (spec, slots)}
        if getattr(model, "latent", None) is not None:
            return PagedLatent, {"latent_dim": model.latent.row}
        if getattr(model, "mixer", "softmax") == "power_retention":
            return PagedState, {
                "state_rows": retention.state_rows(_head_dim(model))}
        return PagedKV, {}

    @classmethod
    def for_model(cls, model, slots: int, block: int,
                  n_pages: Optional[int], max_context: int) -> "KVPager":
        """The pager of ``model``'s kind: the constructor's arguments,
        derived (``n_pages`` None: enough for every slot at
        ``max_context``)."""
        kind, named = cls.kind_of(model, slots)
        return cls(**{
            "n_layers": model.n_layers, "n_kv_heads": model.n_kv_heads,
            "head_dim": _head_dim(model), "block": block,
            "n_pages": (int(n_pages) if n_pages else
                        1 + slots * kind.pages_for(block, max_context)),
            "cache_quant": model.cache_quant,
            "dtype": model.compute_dtype or "float32", **named})

    # -- device pool -----------------------------------------------------
    @property
    def pool(self) -> Tuple:
        """The layer-stacked device arrays the jitted step reads and
        rewrites, as the kind laid them out."""
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

    # -- inside a traced program, over ITS pool -------------------------
    def rows(self, dims, pool, pt, pos, act):
        """The cache object of R rows a slot: ``pt`` [S, MP] i32 the
        slots' page-table rows, ``pos`` [S, R] i32 the rows'
        positions, ``act`` bool broadcastable to [S, R] (False rows
        write nothing a live sequence reads). Its ``pool`` is the pool
        after the rows."""
        return self.cache(dims, pool, pt, pos, act)

    @staticmethod
    def copy_page(pool, src, dst):
        """``pool`` with page ``src`` copied over page ``dst`` (all
        layers, every array): the copy-on-write primitive."""
        return tuple(a.at[:, dst].set(a[:, src]) for a in pool)

    # -- what the kind says, for THIS pool (:class:`_Rows`) -------------
    def pages_for(self, n_tokens: int) -> int:
        return self.cache.pages_for(self.block, n_tokens)

    def prompt_pages(self, slot: int, pages: List[int], tb: int, t0: int):
        return self.cache.prompt_pages(self, slot, pages, tb, t0)

    def prompt_pages_shapes(self, tb: int):
        """:meth:`prompt_pages` as shapes, for lowering."""
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.prompt_pages(0, [0] * (tb // self.block), tb, 1))

    # -- allocation ------------------------------------------------------
    def free_pages(self) -> int:
        return len(self._free)

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
        self.cache.gauge(self)
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
        self.cache.check(self)


# -- the kinds of page: the pool's cache objects, and what each says ---------

def _head_dim(dims) -> int:
    return getattr(dims, "head_dim", None) or dims.hidden // dims.n_heads


#: why neither sharing nor speculation serves a recurrent state
_SNAPSHOTS = ("a recurrent state cannot be adopted at a page boundary nor "
              "rolled back after a rejected draft; both need an index of "
              "state snapshots, which this scheduler does not keep")


class _Rows:
    """A KIND of page: the step's cache object (``attend``, ``pool``:
    the ``decoder_infer`` contract) and, as its class, all that the
    pager and the scheduler ask of the kind (the defaults are KV
    pages'; ``pager`` the :class:`KVPager` of the kind, ``dims`` the
    model)."""
    #: whether a decode step's attention walks KV pages
    walks_kv = False
    #: which of the pool's arrays are recurrent state
    state = slice(0, 0)
    #: the cache class of chunk admission, ``chunk(dims, pool,
    #: carried, where, start, valid)`` (None: the kind admits a prompt
    #: as ONE bucket)
    chunk = None
    #: why no other kind's argument, nor ``cache_quant``, goes with it
    alone = ""
    #: the scheduler's options the kind refuses, each with its reason,
    #: and how the refusal names the model
    refuses: Dict[str, str] = {}
    serves = ""
    #: the devtime scope of the step's blocks
    scope = ""
    #: the bucket prefill's ``attend``, ``prefill(dims, keep)``: what
    #: ``keep(li, *kept)`` gets of a layer :meth:`write_prompt` takes
    prefill = staticmethod(di.causal_prefill)

    def __init__(self, dims, pool, pt, pos, act):
        self.dims = dims
        self.pool = pool
        self.pt = pt
        self.pos = pos
        self.act = act

    @staticmethod
    def pages_for(block: int, n_tokens: int) -> int:
        """Pages that hold ``n_tokens`` cache positions (of
        ``max_context``: a sequence's row of the page table)."""
        return -(-int(n_tokens) // block)

    @staticmethod
    def chunks(dims, max_context: int):
        """How a prompt is admitted, ``(rows, carried)``: by chunks of
        ONE program of ``rows`` rows (``carried`` what they hand on
        beside the pool, at the start); ``rows`` None: by ONE bucket."""
        return None, ()

    @staticmethod
    def prompt_pages(pager, slot: int, pages: List[int], tb: int, t0: int):
        """What :meth:`write_prompt` takes as ``page_ids`` for the
        sequence in ``slot`` with ``pages`` off the free list, a
        bucket of ``tb`` rows and ``t0`` prompt tokens: the first ``tb
        / block`` pages."""
        return jnp.asarray(np.asarray(pages[:tb // pager.block], np.int32))

    @staticmethod
    def prefill_pairs(dims, tb: int, t0: int) -> dict:
        """What the flash kernel spends on a prompt of ``t0`` tokens
        in a bucket of ``tb`` rows, as the admission record's counts:
        ``flash_pairs_need``, the (query, key) pairs its tokens see
        over all layers and heads, and ``flash_pairs_done``, those the
        kernel multiplies for them (``attention.causal_pairs``: the
        kernel's own loop bounds). Nothing where the einsum takes the
        bucket."""
        heads: Dict[Optional[int], int] = {}    # by the layers' window
        for li in range(dims.n_layers):
            w = di.layer_window(dims, li)
            heads[w] = heads.get(w, 0) + di.layer_heads(dims, li)
        need = done = 0
        for w, h in heads.items():
            pairs = causal_pairs(tb, t0, _head_dim(dims),
                                 dims.compute_dtype or "float32", w)
            if pairs is None:
                return {}
            need += h * pairs[0]
            done += h * pairs[1]
        return {"flash_pairs_need": need, "flash_pairs_done": done}

    @classmethod
    def step_reads(cls, pager, dims, at, row_pages: int) -> dict:
        """What a decode step reads, as the step record's counts, from
        the positions ``at`` its live slots write (the host's mirror:
        no device read) and the ``row_pages`` of a slot's row of the
        table; feeds the kind's counters. ``kv_pages``: the pages ONE
        layer's walk reads, the position being written included;
        ``state_bytes``: the live slots' states, once each way."""
        reads = {"kv_pages": int(np.sum(at // pager.block + 1))
                 if cls.walks_kv else 0,
                 "state_bytes": len(at) * pager.state_bytes_per_slot}
        _metrics.SERVING_KV_WALKED.set(reads["kv_pages"])
        _metrics.SERVING_STATE_MOVED.inc(reads["state_bytes"])
        return reads

    @staticmethod
    def check_feed(pager, dims, pt, ends: Dict[int, int]) -> None:
        """The scheduler's guard of a rebuilt feed, for this kind: no
        live slot's last position (``ends``, by slot: its length with
        the budget it has left) reaches past what its row of the table
        ``pt`` serves. Raises :class:`PageTableError`."""
        for i, end in ends.items():
            if pager.pages_for(end) > pt.shape[1]:
                raise PageTableError(
                    f"slot {i} reaches position {end}: its row of "
                    f"{pt.shape[1]} pages serves "
                    f"{pt.shape[1] * pager.block}")

    @staticmethod
    def check(pager) -> None:
        """:meth:`KVPager.check_invariants`, for this kind alone."""

    @staticmethod
    def gauge(pager) -> None:
        """The gauges of this kind alone."""


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
    layer of this pool (a rotation may differ by layer). Float or int8
    (``cache_quant``) is a choice inside this kind."""
    walks_kv = True

    def __init__(self, dims, pool, pt, pos, act, layers=None,
                 turns=None):
        super().__init__(dims, pool, pt, pos, act)
        self.layers = layers
        #: positions a query sees (None: all)
        self.window = None
        #: the program's rotations (``decoder_infer.Turns``: a rule's
        #: cos and sin are made once, for both kinds' layers)
        self.turns = turns or di.Turns(per_row=True)

    @staticmethod
    def alloc(pager, named, dtype) -> Tuple:
        shape = (pager.n_layers, pager.n_pages, pager.block,
                 pager.n_kv_heads, 2 * pager.head_dim)
        if pager.cache_quant == "int8":
            return (jnp.zeros(shape, jnp.int8),
                    jnp.zeros((pager.n_layers, pager.n_pages,
                               pager.n_kv_heads, 2, pager.block),
                              jnp.float32))
        return (jnp.zeros(shape, dtype),)

    @staticmethod
    def write_prompt(dims, pool, page_ids, layers):
        """``pool`` with one sequence's bucket prefill written as whole
        pages: ``layers`` each layer's ``(k, v) [1, Tb, Hkv, D]``
        (``decoder_infer.causal_prefill``'s ``keep``), ``page_ids`` the
        sequence's first ``Tb / block`` pages in position order. The
        pool's own layout, so nothing is transposed on the way, and
        all layers go in one scatter."""
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
        # (the model's layer: a head count or a rotation may differ by it)
        at = None if self.layers is None else self.layers[li]
        theta = (dims.rope_theta if at is None
                 else di.layer_theta(dims, at))
        q, k, v = di.qkv(mha, h, dims,
                         lambda z: self.turns(z, theta, pflat), at)
        n_kv, hd = k.shape[1:]
        if pool[0].ndim == 4:
            return self._attend_folded(li, q, k, v)
        block = pool[0].shape[2]
        q = q.reshape(S, R, -1, hd)
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
    (:class:`PagedWindowed`). The slot's ring is its row of the page
    table, ``ring`` entries long, and
    ``paged_decode_attention(window=)`` reads such a row modulo its
    length: the walk starts at the page of the window's first
    position, masks that page's head and the last one's stale tail,
    and takes ``ring`` pages at most."""

    def __init__(self, dims, pool, pos, act, layers, turns=None):
        ring = (pool[0].shape[1] - 1) // pos.shape[0]
        base = 1 + ring * jnp.arange(pos.shape[0], dtype=jnp.int32)
        super().__init__(dims, pool, base[:, None] + jnp.arange(
            ring, dtype=jnp.int32)[None, :], pos, act, layers, turns)
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


class PagedWindowed(di.ByKind, _Rows):
    """A WINDOWED decoder's pages (``CausalTransformerLM(window=...)``:
    softmax layers of two kinds, ``decoder_infer.WindowSpec``), the
    pool tuple ``(kv_full, kv_window)``, each stacked over the layers
    of its kind (a ``[n_layers, ...]`` of each would pay every kind's
    bytes for every layer), both FOLDED:

    - ``kv_full`` ``[L_full, P, block * Hkv, 2D]``: a full layer keeps
      every position; pages off the free list, reserved at admission
      for the sequence's whole life, as KV pages are;
    - ``kv_window`` ``[L_window, 1 + slots * ring, block * Hkv, 2D]``,
      ``ring = ceil(window / block) + 1``: a window layer's query sees
      the last ``window`` keys, which lie in at most ``ring`` pages, so
      decode slot ``s`` OWNS the ``ring`` pages from ``1 + s * ring`` on
      and writes them as a ring: position ``t`` goes to the slot's page
      ``(t // block) % ring``, over the page that held positions ``ring
      * block`` earlier, all of them out of every later query's window.
      No release in mid-flight, and a sequence can never hold more
      than ``ring`` pages of a window layer. (A second free list used
      as a ring would let short sequences leave pages to long ones; at
      48 slots the ring rows are 2.4 GB of 4.3 GB of pool, and what a
      short sequence leaves unused no admission could take without a
      release in mid-flight, which the scheduler's whole-life
      reservation rules out.)

    A folded page ``[block * Hkv, 2D]`` is the matrix
    ``ops.paged_decode_attention`` reads, stored as that: positions
    and kv heads share the sublane dimension, so the bytes are the
    plain ones whatever ``Hkv`` is. The unfolded ``[block, Hkv, 2D]``
    is the same bytes only where ``Hkv`` fills whole 8-row tiles: for
    4 KV heads the TPU compiler gives it 4-row tiles
    (``T(4,128)(2,1)`` in bf16: still the plain bytes, no padding),
    whose order in memory is not the matrix's (``T(8,128)(2,1)``), and
    the kernel's view of a page would be a copy of the whole pool in
    front of every call.

    As the step's cache object, a full layer's rows go to
    :class:`PagedKV` over the pages of the slot's page-table row, a
    window layer's to :class:`PagedWindowKV` over the slot's ring,
    each under the layer's index among ITS kind and under a scope of
    its kind (``attn.full``, ``attn.window``: the two page walks'
    device times are told apart by it). The kinds may differ in their
    query heads and in their rotary rule (``dims.heads_by_layer``,
    ``dims.rope_by_kind``: a layer's rows are projected and turned by
    ITS layer's, ``decoder_infer.layer_heads`` / ``layer_theta``); the
    KV heads and a head's width are the model's, so the two pools'
    pages are one shape and the walks differ in the query group alone.
    Admission is the bucket prefill; of a window layer's rows it keeps
    the last ``ring`` pages only."""
    walks_kv = True
    alone = ("a windowed pool holds float KV pages of two kinds: "
             "cache_quant, state_rows, latent_dim and ssm do not apply")
    serves = "windowed layers"
    refuses = {
        "prefix_sharing": "a shared page of a window layer would be "
                          "overwritten by its first owner's ring",
        "spec_k": "a rejected draft's row may already have overwritten "
                  "a ring page a later query sees",
        "cache_quant": "a ring page has no int8 form yet"}

    def __init__(self, dims, pool, pt, pos, act):
        spec = dims.windowed
        if pos.shape[1] != 1:
            raise ValueError("a windowed pool serves one position a "
                             "slot (no multi-row program)")
        turns = di.Turns(per_row=True)
        super().__init__(
            spec,
            full=_Scoped(PagedKV(dims, pool[:1], pt, pos, act,
                                 spec.layers("full"), turns), "attn.full"),
            window=_Scoped(PagedWindowKV(dims, pool[1:], pos, act,
                                         spec.layers("window"), turns),
                           "attn.window"))

    @staticmethod
    def alloc(pager, named, dtype) -> Tuple:
        spec, pager.rings = named
        pager.ring = ring_pages(spec.window, pager.block)
        page = (pager.block * pager.n_kv_heads, 2 * pager.head_dim)
        return (jnp.zeros((pager.n_layers, pager.n_pages, *page), dtype),
                jnp.zeros((len(spec.layers("window")),
                           1 + pager.rings * pager.ring, *page), dtype))

    @staticmethod
    def prompt_pages(pager, slot: int, pages: List[int], tb: int, t0: int):
        """Beside the first ``tb / block`` pages, the bucket's pages a
        window layer keeps (``src [ring]``: the last ``ring`` up to
        the one that holds position ``t0 - 1``) and the slot's ring
        pages they go to (``dst [ring]``; the trash page where the
        prompt has fewer)."""
        ring = pager.ring
        src = (t0 - 1) // pager.block - ring + 1 + np.arange(
            ring, dtype=np.int32)
        dst = np.where(src >= 0, 1 + slot * ring + src % ring,
                       0).astype(np.int32)
        return (_Rows.prompt_pages(pager, slot, pages, tb, t0),
                jnp.asarray(np.maximum(src, 0)), jnp.asarray(dst))

    @staticmethod
    def write_prompt(dims, pool, page_ids, layers):
        """Every full layer's pages and, of a window layer's, the last
        ``ring`` (``page_ids`` as :meth:`prompt_pages` gives them)."""
        spec = dims.windowed
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

    @classmethod
    def step_reads(cls, pager, dims, at, row_pages: int) -> dict:
        """Beside ``kv_pages`` (ONE full layer's):
        ``kv_pages_window``, the pages one window layer's walk reads;
        ``kv_rows_read``, the cached positions all layers' walks must
        read (a full layer ``at + 1``, a window layer the last
        ``window`` of them), and ``kv_rows_unwindowed``, what they
        would read with no window; ``ring_overwrites``, the ring pages
        this step begins to write over."""
        spec, block = dims.windowed, pager.block
        n = np.asarray(at, np.int64) + 1
        n_full = len(spec.layers("full"))
        n_win = len(spec.layers("window"))
        first = np.maximum(n - spec.window, 0) // block
        reads = {
            **super().step_reads(pager, dims, at, row_pages),
            "kv_pages_window": int(np.sum(-(-n // block) - first)),
            "kv_rows_read": int(n_full * n.sum() + n_win * np.minimum(
                n, spec.window).sum()),
            "kv_rows_unwindowed": int((n_full + n_win) * n.sum()),
            "ring_overwrites": int(n_win * np.sum(
                ((n - 1) % block == 0)
                & ((n - 1) // block >= pager.ring)))}
        _metrics.SERVING_KV_ROWS_READ.inc(reads["kv_rows_read"])
        _metrics.SERVING_KV_ROWS_UNWINDOWED.inc(
            reads["kv_rows_unwindowed"])
        _metrics.SERVING_RING_OVERWRITES.inc(reads["ring_overwrites"])
        return reads

    @staticmethod
    def check_feed(pager, dims, pt, ends: Dict[int, int]) -> None:
        """And a window layer's ring, which the step cuts from the
        window pool's shape (so its entries are the pool's own), is so
        long that a walk of ``window`` positions wraps once at most."""
        _Rows.check_feed(pager, dims, pt, ends)
        held = pager.pool[1].shape[1]
        window = dims.windowed.window
        need = ring_pages(window, pager.block)
        if (held - 1) // pager.rings < need:
            raise PageTableError(
                f"the window pool's {held} pages give each of "
                f"{pager.rings} slots a ring of "
                f"{(held - 1) // pager.rings}: a window of "
                f"{window} needs {need}")

    @staticmethod
    def check(pager) -> None:
        if pager.pool[1].shape[1] != 1 + pager.rings * pager.ring:
            # what keeps a sequence to ``ring`` pages of a window
            # layer is the pool's shape: a slot's ring IS its pages
            raise PageTableError(
                f"the window pool has {pager.pool[1].shape[1]} pages, "
                f"not the trash page and {pager.rings} rings of "
                f"{pager.ring}")

    @staticmethod
    def gauge(pager) -> None:
        # the slot is the reservation: a sequence's window pages are
        # counted from its full pages, not held anywhere
        held = _metrics.SERVING_KV_PAGES_HELD
        held.labels(kind="full").set(pager.n_pages - 1 - pager.free_pages())
        held.labels(kind="window").set(sum(
            min(len(p), pager.ring) for p in pager._pages_of.values()))


class PagedLatent(_Rows):
    """The pages of a model whose blocks attend through a latent
    (``CausalTransformerLM(mixer="latent")``, ``ops/latent.py``): ONE
    compressed row a position and no KV heads, the pool ``(rows,)``,
    ``[L, P, block, W']`` in the compute dtype: page ``p`` of layer
    ``l`` holds ``block`` consecutive positions' ``[c_kv (normed) |
    k_rope (rotated)]``, the ``[block, W']`` matrix that
    ``ops.latent_decode_attention`` reads with every head at once.
    ``W'`` is ``latent_dim`` rounded up to whole 128-lane tiles, the
    tail zero (``ops.latent.lanes``): the TPU tiles the minor
    dimension by 128 lanes, so a 576-wide row takes 640 in HBM
    whatever the shape says, and the kernel's DMAs move whole tiles
    only. Bytes are counted by ``latent_dim``; pages are counted,
    reserved and freed as KV pages are.

    As the step's cache object, one position a slot: the row's latent
    goes to page ``pt[s, pos // block]`` at offset ``pos % block`` (an
    inactive slot's, and a position past the slot's page table, to the
    trash page), and the ABSORBED form reads the slot's pages as they
    are stored (``ops/latent.py`` has the algebra). Admission is the
    bucket prefill softmax has, with K and V expanded from each
    position's latent (``decoder_infer.latent_prefill``) and the
    latent rows kept as the sequence's pages."""
    alone = ("a latent pool holds one compressed row a position in the "
             "compute dtype: neither cache_quant nor state_rows applies "
             "to it")
    serves = "mixer='latent'"
    refuses = {
        "prefix_sharing": "its multi-row suffix prefill reads KV heads",
        "spec_k": "the verify step's multi-row read has no absorbed "
                  "form yet",
        "cache_quant": "a latent row has no int8 form yet"}
    prefill = staticmethod(di.latent_prefill)

    @staticmethod
    def alloc(pager, named, dtype) -> Tuple:
        return (jnp.zeros((pager.n_layers, pager.n_pages, pager.block,
                           latent.lanes(named)), dtype),)

    @staticmethod
    def write_prompt(dims, pool, page_ids, layers):
        """``layers`` each layer's latent rows ``([1, Tb, latent_dim],)``
        (``decoder_infer.latent_prefill``'s ``keep``)."""
        (rows,) = pool
        lat = jnp.stack([r[0] for (r,) in layers])  # [L, Tb, W]
        n_l, tb, width = lat.shape
        block, stored = rows.shape[2:]
        lat = jnp.pad(lat, ((0, 0), (0, 0), (0, stored - width)))
        return (rows.at[:, page_ids].set(lat.reshape(
            n_l, tb // block, block, stored).astype(rows.dtype)),)

    @staticmethod
    def prefill_pairs(dims, tb: int, t0: int) -> dict:
        """The expanded form's: every head its own keys, padded to
        whole 128-lane tiles (``latent_attention_expanded``)."""
        spec = dims.latent
        pairs = causal_pairs(tb, t0, latent.lanes(spec.nope + spec.rope),
                             dims.compute_dtype or "float32")
        if pairs is None:
            return {}
        heads = dims.n_layers * dims.n_heads
        return {"flash_pairs_need": heads * pairs[0],
                "flash_pairs_done": heads * pairs[1]}

    @classmethod
    def step_reads(cls, pager, dims, at, row_pages: int) -> dict:
        """And ``latent_rows``, the cached positions the step's
        attention reads, the one being written included, with
        ``latent_chunks``, the (slot, chunk) items a layer's walk of
        them has (all but the first issued ahead)."""
        lens = np.asarray(at) + 1
        item = pager.block * latent_chunk_pages(pager.block, row_pages)
        reads = {**super().step_reads(pager, dims, at, row_pages),
                 "latent_rows": int(np.sum(lens)),
                 "latent_chunks": int(np.sum(-(-lens // item)))}
        _metrics.SERVING_LATENT_ROWS.inc(reads["latent_rows"])
        return reads

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
    """The pages of a model whose blocks keep a recurrent state
    instead of a KV cache
    (``CausalTransformerLM(mixer="power_retention")``): ONE fixed-size
    page a sequence whatever its length, the pool ``(S, Z)``:

    - ``S`` ``[L, P, Hkv, rows, d]`` float32: page ``p`` of layer ``l``
      holds each kv head's second-power state in the stored layout of
      ``ops/retention.py`` (``rows`` = ``retention.state_rows(d)``),
      one head's ``[rows, d]`` a contiguous run of whole (8, 128)
      tiles, which is what ``ops.retention_decode`` streams through
      VMEM and writes back in place;
    - ``Z`` ``[L, P, Hkv, d, d]`` float32: the normaliser.

    Nothing is ever shared (a state is no pure function of a prefix's
    tokens alone that another sequence could adopt mid-way: there is
    no chain index to consult). As the step's cache object, one
    position a slot: the slot's state page (``pt``'s only column) is
    updated in place and read; an inactive slot's page is neither.
    Admission runs ONE program of :data:`PREFILL_CHUNK` rows ``ceil(t0
    / chunk)`` times, carrying the state in the sequence's page, so no
    prompt needs a bucket as long as itself."""
    state = slice(None)
    chunk = StateChunk
    alone = ("a recurrent-state pool is float32: cache_quant does not "
             "apply to it")
    serves = "mixer='power_retention'"
    refuses = {"prefix_sharing": _SNAPSHOTS, "spec_k": _SNAPSHOTS}
    scope = "retention_decode"

    @staticmethod
    def alloc(pager, named, dtype) -> Tuple:
        head = (pager.n_layers, pager.n_pages, pager.n_kv_heads)
        # by the logical size (d (d + 1) / 2 rows of d values and the
        # normaliser's, float32, both ways)
        pager.state_bytes_per_slot = (
            2 * 4 * pager.n_layers * pager.n_kv_heads
            * retention.logical_state_rows(pager.head_dim)
            * (pager.head_dim + 1))
        return (jnp.zeros((*head, named, pager.head_dim), jnp.float32),
                jnp.zeros((*head, pager.head_dim, pager.head_dim),
                          jnp.float32))

    @staticmethod
    def pages_for(block: int, n_tokens: int) -> int:
        return 1        # ONE state page a sequence, whatever its length

    @staticmethod
    def chunks(dims, max_context: int):
        """Chunks of :data:`PREFILL_CHUNK` rows, which hand on the
        prompt as its later chunks read it: every layer's keys, values
        and cumulative log-gates, for prompts up to ``max_context``."""
        rows = min(PREFILL_CHUNK, max_context)
        return rows, retention.zero_history(
            dims.n_layers, -(-max_context // rows) * rows,
            dims.n_kv_heads, _head_dim(dims),
            dims.compute_dtype or "float32")

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
    """One position a slot against a hybrid's state pool ``(H, tail)``
    (:class:`PagedHybrid`): the slot's state is updated in place and
    read (``ops.ssm_decode``), its convolution's tail moved on by one
    row. An inactive slot's state page is neither read nor written,
    and its tail goes to the trash page. ``li`` counts the Mamba
    layers (``decoder_infer.ByKind``)."""

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


class PagedHybrid(di.ByKind, _Rows):
    """A HYBRID decoder's pages (``CausalTransformerLM(mixer=
    "hybrid")``: Mamba-2 state-space layers beside softmax attention
    layers, ``ops/ssm.py``), the pool tuple ``(kv, H, tail)``, each
    array stacked over the layers of ITS kind only:

    - ``kv`` ``[L_attn, P, block, Hkv, 2D]``: KV pages, for the
      attention layers; allocated, reserved and freed by the free list;
    - ``H`` ``[L_ssm, 1 + slots, N, H P]`` float32: a sequence's state
      in each Mamba layer, the stored matrix of ``ops/ssm.py`` (row
      ``n`` the state value ``n`` of every (head, feature) column),
      which ``ops.ssm_decode`` streams through VMEM and writes back in
      place;
    - ``tail`` ``[L_ssm, 1 + slots, (K - 1) * channels]`` in the
      compute dtype: the last ``K - 1`` un-convolved rows of each
      layer's convolution, flat (one page is one lane-aligned run).

    A sequence's ONE state page is ``slot + 1`` (page 0 the trash
    page); it comes to its next sequence as its last one left it:
    admission starts from an empty state when ``start`` is 0. As the
    step's cache object, an attention layer's rows go to
    :class:`PagedKV` over the KV pages, a Mamba layer's to
    :class:`PagedSSM` over the state pages, each under the layer's
    index among ITS kind. Admission is the chunk program
    (``hybrid.chunk`` rows, :class:`HybridChunk`)."""
    walks_kv = True
    state = slice(1, None)
    chunk = HybridChunk
    alone = ("a hybrid pool holds float KV pages beside float32 state "
             "pages: cache_quant, state_rows and latent_dim do not apply")
    serves = "mixer='hybrid'"
    refuses = {"prefix_sharing": _SNAPSHOTS, "spec_k": _SNAPSHOTS}

    def __init__(self, dims, pool, pt, pos, act):
        super().__init__(
            dims.hybrid, softmax=PagedKV(dims, pool[:1], pt, pos, act),
            mamba2=PagedSSM(dims, pool[1:], act))

    @staticmethod
    def alloc(pager, named, dtype) -> Tuple:
        spec, slots = named
        n_ssm = len(spec.layers("mamba2"))
        tail = (spec.d_conv - 1) * spec.conv_dim
        # the state and the tail of every Mamba layer, both ways
        pager.state_bytes_per_slot = 2 * n_ssm * (
            4 * spec.d_state * spec.d_inner + tail * dtype.itemsize)
        return PagedKV.alloc(pager, None, dtype) + (
            jnp.zeros((n_ssm, 1 + slots, spec.d_state, spec.d_inner),
                      jnp.float32),
            jnp.zeros((n_ssm, 1 + slots, tail), dtype))

    @staticmethod
    def chunks(dims, max_context: int):
        return min(dims.hybrid.chunk, max_context), ()

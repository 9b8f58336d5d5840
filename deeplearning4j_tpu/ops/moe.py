"""The expert layer of a mixture-of-experts block, as ONE chip of an
expert-parallel group computes it: told which experts it holds, it
routes over ALL the published experts and computes its own experts'
part of the result, beside the shared expert. Experts a token chose
that are not held here add nothing (their chips would); no token is
dropped, no route capped, and there is no ``[T, E, C]`` tensor. On one
chip the layer runs without its exchange, and nothing stands in for
it. Plain ``jnp``.

Routing (DeepSeek-V3, ``noaux_tc``): ``s = sigmoid(h_f32 W_r)`` over
all ``n_routed`` outputs, float32 at full matmul precision; the choice
goes by ``s + b`` (``b`` the score-correction bias): a group's score
is the sum of its two largest, the best ``topk_group`` of ``n_group``
groups stay, the best ``top_k`` experts inside them are chosen (ties
to the lower index); the weights are the chosen experts' ``s``
(without ``b``), normalised to sum 1, times ``scale``.

A second scoring rule, ``score="softmax_topk"`` (no groups, no bias):
the ``top_k`` largest router logits are chosen (ties to the lower
index) and weighed by the softmax over THEM, which is the softmax
over all outputs renormalised over the chosen. The rows the router
reads may differ from the rows the experts multiply (``route_rows``:
a block whose router sits before its attention hands the
pre-attention normed rows; ``ExpertSpec.route_before_mixer``).

``y = shared(h) + sum_{e chosen, e held here} w_e expert_e(h)``, each
a gated unit: SwiGLU (``unit="swiglu"``, ``silu(h Wg) * (h Wu)``) or
ReGLU (``unit="reglu"``, ``relu`` in ``silu``'s place). Parameters of
one layer (``pblk["moe"]``): ``Wr [F,
n_routed]`` and ``br [n_routed]`` (float32, whatever the compute
dtype), the held experts' ``Weg``/``Weu [n_held, F, W]`` and ``Wed
[n_held, W, F]``, the shared expert's ``Wsg``/``Wsu [F, n_shared W]``
and ``Wsd``.

:func:`experts` is the serving form: held token-expert pairs sorted by
expert and multiplied a tile of rows at a time against that expert's
matrices, over the tiles the routing filled (an expert nobody chose
costs no read of its weights). It has two forms, one algorithm at two
sizes of tile whose fixed cost weighs differently:

- the LOOP (:func:`_experts_loop`, plain ``jnp``): a ``fori_loop`` of
  data-dependent length whose body takes its expert's matrices by a
  dynamic index and adds its rows into the pairs' output. XLA runs a
  ``while`` body's ops one after another, so a tile pays its weights'
  read in full and some 10 us of small ops besides: a tenth of a tile
  of 88 MB, two fifths of one of 12 MB;
- the KERNEL (:func:`_experts_tiled`, Pallas): every group padded to
  whole tiles, so that a tile belongs to ONE expert and each row is
  written once; the grid walks the tiles, a scalar-prefetched table
  names each tile's expert, and the weights come by ``BlockSpec``:
  the pipeline fetches tile ``i + 1``'s expert while tile ``i`` is
  multiplied, and consecutive tiles of one expert name the same block,
  so an expert's weights are fetched ONCE however many tiles it fills.
  The gated unit runs inside (float32 products, the unit in float32,
  rounded to the compute dtype once, before the down projection).

The rule between them (:func:`_use_expert_kernel`) is read from the
operands at trace time: the platform gate, ``F`` and ``W`` whole
128-lane tiles, a float compute dtype, and one expert's three matrices
small enough to lie in VMEM twice over (:data:`_EXPERT_MAX_BYTES`).
Above that the loop runs. Which form a traced layer took is tallied
(``dl4j_tpu_moe_expert_layers_traced_total{path=}``).
:func:`experts_plain` applies every held expert to every row and
masks by the routing: the form autodiff runs (``fit`` at test size),
and the others' check.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.obs import devtime

#: leaves of a parameter tree that stay float32 whatever the compute
#: dtype: the router is published in float32, and a bf16 router
#: chooses other experts at near ties
FLOAT32_LEAVES = ("Wr", "br")


#: the routing rules: DeepSeek-V3's ``noaux_tc`` (sigmoid scores, a
#: correction bias, groups), or the ``top_k`` largest logits weighed
#: by the softmax over them
SCORES = ("sigmoid_groups", "softmax_topk")

#: the gate's activation of an expert's unit ``act(h Wg) * (h Wu)``
UNITS = {"swiglu": jax.nn.silu, "reglu": jax.nn.relu}


@dataclass(frozen=True)
class ExpertSpec:
    """The expert layers of a model, and this chip's share of them:
    ``width`` of a routed expert, ``n_held`` experts held here from
    ``offset`` on (this chip's rank times ``n_held``), of ``n_routed``
    published; ``n_shared`` shared experts; ``top_k`` a token, chosen
    within the best ``topk_group`` of ``n_group`` groups; ``scale``
    the routed scaling factor; ``first_dense`` leading layers keep a
    dense feed-forward. ``score`` is the routing rule
    (:data:`SCORES`), ``unit`` the experts' gated unit
    (:data:`UNITS`); ``route_before_mixer``: the router reads the
    block's PRE-attention normed rows, not the rows the experts
    multiply."""
    width: int
    n_held: int
    n_routed: int
    top_k: int
    n_group: int = 1
    topk_group: int = 1
    scale: float = 1.0
    n_shared: int = 1
    offset: int = 0
    first_dense: int = 0
    score: str = "sigmoid_groups"
    unit: str = "swiglu"
    route_before_mixer: bool = False

    def __post_init__(self):
        if self.score not in SCORES:
            raise ValueError(f"score={self.score!r} ({' | '.join(SCORES)})")
        if self.unit not in UNITS:
            raise ValueError(f"unit={self.unit!r} ({' | '.join(UNITS)})")
        if self.n_routed % self.n_group:
            raise ValueError(f"n_routed={self.n_routed} not divisible "
                             f"by n_group={self.n_group}")
        if not 0 <= self.offset <= self.n_routed - self.n_held:
            raise ValueError(
                f"experts {self.offset}..{self.offset + self.n_held - 1}"
                f" are not among the {self.n_routed} published")
        if self.top_k > self.topk_group * (self.n_routed
                                           // self.n_group):
            raise ValueError("top_k exceeds the kept groups' experts")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def of(cls, value) -> "ExpertSpec":
        return value if isinstance(value, cls) else cls(**dict(value))


def route(h, w_r, bias, *, n_group: int, topk_group: int, top_k: int,
          scale: float, score: str = "sigmoid_groups"):
    """Rows ``h [T, F]`` to ``ids [T, top_k]`` i32 over ALL of
    ``w_r``'s outputs and their weights ``[T, top_k]`` float32."""
    with devtime.scope("ops.moe_route"):
        s = jnp.dot(h.astype(jnp.float32), w_r.astype(jnp.float32),
                    precision=lax.Precision.HIGHEST)
        if score == "softmax_topk":
            top, ids = lax.top_k(s, top_k)
            w = jax.nn.softmax(top, axis=-1)
            return ids.astype(jnp.int32), (w if scale == 1.0
                                           else w * scale)
        s = jax.nn.sigmoid(s)
        c = s + bias.astype(jnp.float32)
        t, e = c.shape
        per = e // n_group
        group = lax.top_k(c.reshape(t, n_group, per), 2)[0].sum(-1)
        _, kept = lax.top_k(group, topk_group)              # [T, kept]
        keep = jnp.any(kept[:, :, None]
                       == jnp.arange(n_group)[None, None, :], axis=1)
        c = jnp.where(jnp.repeat(keep, per, axis=1), c, -jnp.inf)
        _, ids = lax.top_k(c, top_k)
        w = jnp.take_along_axis(s, ids, axis=1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
        return ids.astype(jnp.int32), w


def gated(h, wg, wu, wd, unit: str = "swiglu"):
    """One gated unit over rows ``h``: ``(act(h Wg) * (h Wu)) Wd``."""
    return (UNITS[unit](h @ wg) * (h @ wu)) @ wd


#: rows an expert layer routes at a time (the serving form): no bucket
#: of the cells that came before is longer
ROUTE_BLOCK = 4096


def _tile_rows(n_pairs: int) -> int:
    """Rows of a tile: near the mean size of a held expert's group
    when a sixteenth of the pairs are held, a power of two from 16
    (a packed bf16 sublane tile) to 128."""
    rows = 16
    while rows < 128 and rows * 256 < n_pairs:
        rows *= 2
    return rows


def experts(h, p, ids, weights, held, unit: str = "swiglu"):
    """The held experts' part of the layer for rows ``h [T, F]``
    routed by ``ids``/``weights`` (:func:`route`); ``held`` is this
    chip's static ``(offset, count)``, ``unit`` the experts' gated
    unit. Returns ``(y [T, F], counts
    [count] i32)``: ``counts[e]`` is the pairs held expert ``e``
    computed. The kernel where :func:`_use_expert_kernel` takes the
    operands, else the loop; the traced layer's path is tallied."""
    from deeplearning4j_tpu.obs import metrics
    from deeplearning4j_tpu.perf import sentry
    kernel = _use_expert_kernel(h, p)
    path = "kernel" if kernel else "loop"
    metrics.MOE_EXPERT_LAYERS.labels(path=path).inc()
    sentry.note_traced(f"expert_layers_{path}", expert_f=h.shape[-1],
                       expert_w=p["Weg"].shape[-1], expert_held=held[1])
    with devtime.scope("ops.moe_experts"):
        if kernel:
            return _experts_tiled(h, p, ids, weights, held, unit)
        return _experts_loop(h, p, ids, weights, held, unit)


def _sorted_pairs(ids, held):
    """The ``T top_k`` pairs by held expert: ``mine [T, top_k]`` (the
    pair's expert is held here), ``order [n]`` (the pairs sorted by
    held expert, stably; pairs of experts not held last) and ``sizes
    [count]`` i32 (the pairs of each held expert)."""
    offset, count = held
    n = ids.shape[0] * ids.shape[1]
    local = ids - offset
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count).reshape(n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                    axis=0, dtype=jnp.int32)
    return mine, order, sizes


def _sorted_rank(order):
    """Where each pair stands among the sorted: ``order``'s inverse."""
    n = order.shape[0]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))


def _experts_loop(h, p, ids, weights, held, unit):
    """:func:`experts` by the loop.

    The ``T top_k`` pairs are sorted by held expert (pairs of experts
    not held go last and are never multiplied); a group's rows are
    taken ``_tile_rows`` at a time, gathered from ``h``, multiplied
    and added into the pairs' output rows, which the tokens then
    gather back and weigh. Every size is static (all pairs have a
    row: nothing can be dropped); only the loop's length follows the
    routing."""
    t, k = ids.shape
    n, f = t * k, h.shape[-1]
    mine, order, sizes = _sorted_pairs(ids, held)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    bt = _tile_rows(n)
    tiles = (sizes + bt - 1) // bt
    tile_ends = jnp.cumsum(tiles)
    tok = jnp.concatenate([(order // k).astype(jnp.int32),
                           jnp.zeros((bt,), jnp.int32)])

    def tile(i, out):
        e = jnp.sum(tile_ends <= i, dtype=jnp.int32)
        r0 = starts[e] + (i - (tile_ends[e] - tiles[e])) * bt
        live = (r0 + jnp.arange(bt)) < ends[e]
        x = h[lax.dynamic_slice(tok, (r0,), (bt,))]
        y = gated(x, *(lax.dynamic_index_in_dim(p[w], e, 0, False)
                       for w in ("Weg", "Weu", "Wed")), unit=unit)
        y = jnp.where(live[:, None], y, jnp.zeros_like(y))
        # a tile's dead rows lie over the next group's: added as
        # zeros, whichever of the two tiles comes first
        cur = lax.dynamic_slice(out, (r0, 0), (bt, f))
        return lax.dynamic_update_slice(out, cur + y, (r0, 0))

    out = lax.fori_loop(0, tile_ends[-1], tile,
                        jnp.zeros((n + bt, f), h.dtype))
    where = _sorted_rank(order).reshape(t, k)
    w = jnp.where(mine, weights, 0.0)
    y = jnp.zeros((t, f), jnp.float32)
    for j in range(k):
        y = y + w[:, j, None] * out[where[:, j]].astype(jnp.float32)
    return y.astype(h.dtype), sizes


#: one expert's three matrices, at most, for the kernel to take them:
#: they lie in VMEM twice over (this tile's and the next's) beside a
#: tile's rows in and out and its float32 products, some 5 MB at 128
#: rows, of the 128 MiB a v5e core has. SmallThinker's expert is 11.8
#: MB, DeepSeek-V3's 88.1 MB
_EXPERT_MAX_BYTES = 32 * 1024 * 1024


def _use_expert_kernel(h, p) -> bool:
    """The dispatch line of :func:`experts`, decided at trace time from
    the operands: the platform gate every kernel uses
    (``kernel_registry.gate_active``), a float compute dtype the
    weights share, ``F`` and ``W`` whole 128-lane tiles (a block is
    an expert's whole matrix, and Mosaic slices whole tiles only) and
    an expert small enough to be double-buffered whole
    (:data:`_EXPERT_MAX_BYTES`)."""
    from deeplearning4j_tpu.ops.kernel_registry import gate_active
    _, f, w = p["Weg"].shape
    return (gate_active("moe_experts")
            and h.dtype in (jnp.bfloat16, jnp.float32)
            and all(p[m].dtype == h.dtype for m in ("Weg", "Weu", "Wed"))
            and f % 128 == 0 and w % 128 == 0
            and 3 * f * w * h.dtype.itemsize <= _EXPERT_MAX_BYTES)


def _kernel_tile_rows(n_pairs: int, count: int) -> int:
    """Rows of the kernel's tile: near the mean size of a HELD
    expert's group, a power of two from 16 (a packed bf16 sublane
    tile) to 128. An expert's weights are fetched once however many
    tiles it fills, so a larger tile buys only a fetch better hidden
    (the pipeline looks one tile ahead; a 4,096-row block of the v5e
    cell: the kernel 2.03 ms a layer at 512 rows against 2.29 at 128)
    and pays it back in the padded rows it gathers (the whole layer
    3.85 against 3.88 ms), the memory they take and the size of the
    program."""
    rows = 16
    while rows < 128 and rows * count < n_pairs:
        rows *= 2
    return rows


def _expert_tiles_kernel(e_ref, n_ref, x_ref, wg_ref, wu_ref, wd_ref,
                         o_ref, *, unit):
    """One tile of ``bt`` rows, all of ONE expert, whose three
    matrices the pipeline has brought: the gated unit with float32
    products, rounded once in front of the down projection. A grid
    step past the routing's last tile does nothing (its blocks are
    the last live tile's, already resident)."""
    del e_ref

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        a = (UNITS[unit](g) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(
            a, wd_ref[0],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _expert_tiles_call(x, wg, wu, wd, tile_expert, n_live, *, bt, unit,
                       interpret):
    """``x [n_tiles bt, F]``, tile ``i`` the rows of expert
    ``tile_expert[i]``, through that expert's unit: ``[n_tiles bt,
    F]``. Only the first ``n_live[0]`` tiles are multiplied and
    written; the rest name the last live tile's blocks, so that no
    copy is issued for them."""
    rows, f = x.shape
    w = wg.shape[-1]
    size = x.dtype.itemsize

    def row_block(i, e, n):
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0)), 0

    def expert_block(i, e, n):
        return e[i], 0, 0

    # both buffers of the three matrices and of the rows in and out,
    # the float32 products, and room for the compiler's own
    vmem = (2 * 3 * f * w * size + 4 * bt * f * size
            + 4 * bt * (2 * w + f) + (8 << 20))
    return pl.pallas_call(
        functools.partial(_expert_tiles_kernel, unit=unit),
        out_shape=jax.ShapeDtypeStruct((rows, f), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // bt,),
            in_specs=[pl.BlockSpec((bt, f), row_block),
                      pl.BlockSpec((1, f, w), expert_block),
                      pl.BlockSpec((1, f, w), expert_block),
                      pl.BlockSpec((1, w, f), expert_block)],
            out_specs=pl.BlockSpec((bt, f), row_block)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem),
        name="moe_expert_tiles",
        interpret=interpret,
    )(tile_expert, n_live, x, wg, wu, wd)


def _pick(table, idx):
    """``table[idx]`` for a short ``table`` and many ``idx`` as a
    one-hot select and sum (0 where ``idx`` is past the table): an
    element gather of thousands of indices is a megabyte of program
    on the TPU."""
    hot = idx[:, None] == jnp.arange(table.shape[0])[None, :]
    return jnp.sum(jnp.where(hot, table[None, :], 0), axis=1)


def _experts_tiled(h, p, ids, weights, held, unit):
    """:func:`experts` by the kernel.

    The sorted pairs are laid out with every group padded to whole
    tiles of ``_kernel_tile_rows``: a static ``(n + count (bt - 1)) //
    bt`` tiles hold them whatever the routing. The pairs' rows are
    gathered from ``h`` once, the kernel multiplies the tiles the
    routing filled, each row written once, and the tokens gather their
    pairs' rows back (once, all of them) and weigh them. Rows of
    padding and of tiles past the last are never read back: every size
    is static and nothing can be dropped."""
    from deeplearning4j_tpu.ops import pallas_kernels
    offset, count = held
    t, k = ids.shape
    n, f = t * k, h.shape[-1]
    mine, order, sizes = _sorted_pairs(ids, held)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    bt = _kernel_tile_rows(n, count)
    n_tiles = (n + count * (bt - 1)) // bt      # sum of ceil(size / bt)
    tiles = (sizes + bt - 1) // bt
    tile_ends = jnp.cumsum(tiles)
    tile_starts = tile_ends - tiles
    n_live = tile_ends[-1]
    # a sorted row's padded row lies `shift` of its group further on
    shift = tile_starts * bt - starts
    # tile i's expert; a tile past the last live one names the last
    # live tile's, so that its weights are not fetched again
    i = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_live - 1, 0))
    e = jnp.minimum(jnp.sum(tile_ends[None, :] <= i[:, None], axis=1,
                            dtype=jnp.int32), count - 1)
    # a tile's rows are consecutive sorted pairs from its first on (a
    # group's padding reads the rows that follow it, tokens of the
    # next group or token 0: computed and never read back)
    tok = jnp.concatenate([(order // k).astype(jnp.int32),
                           jnp.zeros((bt,), jnp.int32)])
    tok = tok[((i * bt - _pick(shift, e))[:, None]
               + jnp.arange(bt)).reshape(-1)]
    out = _expert_tiles_call(
        h[tok], p["Weg"], p["Weu"], p["Wed"], e,
        n_live.reshape(1), bt=bt, unit=unit,
        interpret=pallas_kernels._interpret())
    # a pair's row; a pair of an expert not held has none (what lies
    # at `where` there may never have been written: masked below)
    where = _sorted_rank(order) + _pick(
        shift, jnp.where(mine, ids - offset, count).reshape(n))
    # ONE gather of all pairs' rows, the j-th pairs of all tokens
    # together (token by token it takes half as long again), kept a
    # gather of its own in front of the weighing
    rows = lax.optimization_barrier(
        out[where.reshape(t, k).T.reshape(n)]).reshape(k, t, f)
    rows = jnp.where(mine.T[:, :, None], rows, jnp.zeros_like(rows))
    y = jnp.sum(weights.T[:, :, None] * rows.astype(jnp.float32), axis=0)
    return y.astype(h.dtype), sizes


def experts_plain(h, p, ids, weights, held, unit: str = "swiglu"):
    """:func:`experts` by the plain form: every held expert applied
    to every row, masked by the routing."""
    offset, count = held
    chose = (ids - offset)[..., None] == jnp.arange(count)  # [T, K, E]
    w = jnp.sum(weights[..., None] * chose, axis=1)         # [T, E]
    g = jnp.einsum("tf,efw->etw", h, p["Weg"])
    u = jnp.einsum("tf,efw->etw", h, p["Weu"])
    y = jnp.einsum("etw,ewf->etf", UNITS[unit](g) * u, p["Wed"])
    out = jnp.einsum("te,etf->tf", w, y.astype(jnp.float32))
    return out.astype(h.dtype), jnp.sum(chose, axis=(0, 1),
                                        dtype=jnp.int32)


def layer(p, h, spec: ExpertSpec, plain: bool = False, live=None,
          route_rows=None):
    """One expert layer over rows ``h [..., F]``: ``(y, counts)``,
    ``y = shared(h) + this chip's experts' part`` and ``counts
    [n_held]`` the held experts' pairs. ``live`` (bool, ``h``'s
    leading shape) marks the rows that carry a token: a bucket's
    padding and a slot without a sequence make no pair (they all hold
    one token and would all choose the same experts: whole tiles of
    work for rows nobody reads, more or fewer by the luck of that
    token's route). ``route_rows`` (``h``'s shape) are the rows the
    router reads where they are not ``h`` itself."""
    rows = h.reshape(-1, h.shape[-1])
    n = rows.shape[0]
    if not plain and n > ROUTE_BLOCK and n % ROUTE_BLOCK == 0:
        # a long bucket's rows, a block at a time: the sorted pairs'
        # rows [block top_k, F] bound what the layer holds beside them
        shaped = lambda z: None if z is None else z.reshape(
            n // ROUTE_BLOCK, ROUTE_BLOCK, *z.shape[h.ndim - 1:])
        y, counts = lax.map(
            lambda b: layer(p, b[0], spec, live=b[1], route_rows=b[2]),
            (shaped(h), shaped(live), shaped(route_rows)))
        return y.reshape(h.shape), jnp.sum(counts, axis=0)
    ids, weights = route(
        rows if route_rows is None else route_rows.reshape(rows.shape),
        p["Wr"], p["br"], n_group=spec.n_group,
        topk_group=spec.topk_group, top_k=spec.top_k, scale=spec.scale,
        score=spec.score)
    if live is not None:
        ids = jnp.where(live.reshape(-1, 1), ids, -1)   # held nowhere
    held = (spec.offset, spec.n_held)
    if plain:
        with devtime.scope("ops.moe_experts"):
            y, counts = experts_plain(rows, p, ids, weights, held,
                                      unit=spec.unit)
    else:
        y, counts = experts(rows, p, ids, weights, held, unit=spec.unit)
    if "Wsg" in p:
        with devtime.scope("ops.moe_shared"):
            y = gated(rows, p["Wsg"], p["Wsu"], p["Wsd"],
                      unit=spec.unit) + y
    return y.reshape(h.shape), counts

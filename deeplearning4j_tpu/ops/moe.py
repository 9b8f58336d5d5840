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
matrices, in a loop whose length is the number of tiles the routing
filled (an expert nobody chose costs no read of its weights).
:func:`experts_plain` applies every held expert to every row and
masks by the routing: the form autodiff runs (``fit`` at test size),
and the other's check.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

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
    computed.

    The ``T top_k`` pairs are sorted by held expert (pairs of experts
    not held go last and are never multiplied); a group's rows are
    taken ``_tile_rows`` at a time, gathered from ``h``, multiplied
    and added into the pairs' output rows, which the tokens then
    gather back and weigh. Every size is static (all pairs have a
    row: nothing can be dropped); only the loop's length follows the
    routing."""
    offset, count = held
    t, k = ids.shape
    n, f = t * k, h.shape[-1]
    local = ids - offset
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count).reshape(n)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                    axis=0, dtype=jnp.int32)
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
    where = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32)).reshape(t, k)
    w = jnp.where(mine, weights, 0.0)
    y = jnp.zeros((t, f), jnp.float32)
    for j in range(k):
        y = y + w[:, j, None] * out[where[:, j]].astype(jnp.float32)
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
    with devtime.scope("ops.moe_experts"):
        y, counts = (experts_plain if plain else experts)(
            rows, p, ids, weights, (spec.offset, spec.n_held),
            unit=spec.unit)
    if "Wsg" in p:
        with devtime.scope("ops.moe_shared"):
            y = gated(rows, p["Wsg"], p["Wsu"], p["Wsd"],
                      unit=spec.unit) + y
    return y.reshape(h.shape), counts

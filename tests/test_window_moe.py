"""A decoder whose softmax layers are of two kinds, sliding-window
layers with rotary positions beside full layers without positions, and
whose feed-forward is a set of small ReGLU experts routed by the
pre-attention rows (``CausalTransformerLM(window=..., window_layers=...,
rope_layers=..., experts=ExpertSpec(score="softmax_topk", unit="reglu",
route_before_mixer=True))``): the windowed kernels against the masked
plain form at the window's edge, the expert layer's new rule against
its plain form and the benchmark's plain reference, the pager's two
kinds of KV pages, the model's forwards, and the gateway's
prefill-then-decode through the ring held against the reference's full
forward AT THE LOGITS.

Toy widths: window 32, block 8 (a ring of 5 pages), contexts to 100 so
that a ring wraps at least twice; 8 experts, 2 a token.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.nn.layers import attention as A
from deeplearning4j_tpu.ops import moe as M
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.serving import DecodeScheduler, ServingGateway
from deeplearning4j_tpu.serving import kv_pager
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

WINDOW, BLOCK = 32, 8
EXPERTS = M.ExpertSpec(width=24, n_held=8, n_routed=8, top_k=2,
                       n_shared=0, score="softmax_topk", unit="reglu",
                       route_before_mixer=True)
LAYOUT = [0, 1, 1, 1]
#: the benchmark's names for the same sizes (the reference reads these)
_TOY = dict(num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=24, hidden_size=64,
            rope_theta=1.5e6, rope_layout=LAYOUT,
            sliding_window_layout=LAYOUT, sliding_window_size=WINDOW,
            rms_norm_eps=1e-6, moe_num_active_primary_experts=2,
            moe_num_primary_experts=8,
            moe_primary_router_apply_softmax=True, norm_topk_prob=True)


def _model(**kw):
    on = [i for i, x in enumerate(LAYOUT) if x]
    kw.setdefault("updater", upd.Sgd(learning_rate=0.05))
    return CausalTransformerLM(
        vocab_size=64, hidden=64, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=24, max_len=128, rope_theta=1.5e6, norm_eps=1e-6,
        window=WINDOW, window_layers=on, rope_layers=on,
        experts=EXPERTS, seed=11, **kw)


@pytest.fixture(scope="module")
def window_lm():
    model = _model()
    net = model.init(seq_len=64)
    # biases and gains off their initial values, so that a term left
    # out shows
    key = jax.random.PRNGKey(2)
    flat, treedef = jax.tree_util.tree_flatten(net.params)
    net.params = jax.tree_util.tree_unflatten(treedef, [
        a + 0.05 * jax.random.normal(jax.random.fold_in(key, i), a.shape,
                                     a.dtype)
        if a.ndim == 1 and a.shape[0] != 8 else a
        for i, a in enumerate(flat)])
    for i in range(1, 5):       # the published model has no biases
        net.params[f"layer_{i}"]["mha"]["bo"] = jnp.zeros((64,))
        net.params[f"layer_{i}"]["moe"]["br"] = jnp.zeros((8,))
    return model, net


# -- the windowed kernels against the masked plain form ----------------------

def _masked_plain(q, k, v, window):
    """Query t of [T] sees keys t - window < j <= t, literally."""
    q, k, v = (np.asarray(z, np.float64) for z in (q, k, v))
    b, t, h, d = q.shape
    g = h // k.shape[2]
    out = np.zeros_like(q)
    for i in range(t):
        lo = max(0, i - window + 1)
        for hh in range(h):
            s = k[:, lo:i + 1, hh // g] @ q[:, i, hh][..., None]
            s = s[..., 0] / np.sqrt(d)
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[:, i, hh] = np.einsum("bt,btd->bd", w,
                                      v[:, lo:i + 1, hh // g])
    return out


@pytest.mark.parametrize("window,heads", [(WINDOW, 4), (8, 12), (8, 16)],
                         ids=["two_blocks_wide", "narrower_than_a_block_6",
                              "narrower_than_a_block_8"])
@pytest.mark.parametrize("t", [31, 32, 33, 100],
                         ids=["one_short", "edge", "one_past", "long"])
def test_flash_window_matches_the_masked_plain_form(t, window, heads):
    """``flash_attention(window=)`` in interpret mode, 16-row blocks so
    that whole KV blocks fall out of range, against a literal masked
    softmax; the plain einsum form (what autodiff runs) beside it. A
    window NARROWER than a KV block (8 keys in blocks of 16: a query
    block then skips every KV block but its own and the one before
    it), with query groups of 6 and of 8 heads a KV head, is what a
    decoder whose window layers hold 512 keys gives the kernel's
    larger blocks."""
    rng = np.random.default_rng(t)
    q = jnp.asarray(rng.standard_normal((2, t, heads, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, t, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, t, 2, 16)), jnp.float32)
    want = _masked_plain(q, k, v, window)
    got = pk.flash_attention(q, k, v, causal=True, window=window,
                             block_q=16, block_k=16)
    assert np.abs(np.asarray(got) - want).max() < 2e-5
    plain = A.plain_attention(q, k, v, causal=True, window=window)
    assert np.abs(np.asarray(plain) - want).max() < 2e-5
    # and the window is a window: the unwindowed form differs past it
    whole = pk.flash_attention(q, k, v, causal=True, block_q=16,
                               block_k=16)
    assert (np.abs(np.asarray(whole) - want).max() > 1e-3) == (t > window)


def test_flash_window_is_causal_only():
    q = jnp.zeros((1, 16, 2, 16))
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(q, q, q, causal=False, window=8)


@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "bidirectional"])
def test_unwindowed_flash_backward_traces_its_split_kernels(
        monkeypatch, causal):
    """The window is the forward's alone: the backward of the
    UNWINDOWED flash on its long-sequence path (separate dQ and dK/dV
    kernels, taken past ``_FUSED_BWD_DQ_VMEM``; the threshold is
    forced so that a test size takes it) traces and equals autodiff
    on the plain form. The other split-path test is a slow one, so
    without this a name the dQ kernel does not have passes tier 1."""
    monkeypatch.setattr(pk, "_FUSED_BWD_DQ_VMEM", 0)
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((1, 70, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 70, 1, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 70, 1, 16)), jnp.float32)
    co = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(pk.flash_attention(
        *a, causal=causal, block_q=32, block_k=32) * co),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(A.plain_attention(
        *a, causal=causal) * co), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


def _ring_case(rng, lengths, n_kv=4, d=128, block=16, window=32,
               layers=2):
    """A folded ring pool as decode leaves it: every slot's positions
    written in turn, position t to ring page (t // block) % ring, over
    stale finite rows (a page is zeros or an earlier lap's rows, never
    NaN); the trash page and the ring pages the last query's walk must
    not touch hold NaN."""
    ring = kv_pager.ring_pages(window, block)
    s_ = len(lengths)
    pool = rng.standard_normal(
        (layers, 1 + s_ * ring, block * n_kv, 2 * d)).astype(np.float32)
    pool[:, 0] = np.nan
    kv = rng.standard_normal((layers, s_, max(lengths) + 1, n_kv, 2 * d))
    for s, n in enumerate(lengths):
        for t in range(n):
            page = 1 + s * ring + (t // block) % ring
            row = (t % block) * n_kv
            pool[:, page, row:row + n_kv] = kv[:, s, t]
        walked = {p % ring for p in range(max(n - window, 0) // block,
                                          -(-n // block))}
        for r in set(range(ring)) - walked:
            pool[:, 1 + s * ring + r] = np.nan
    return pool, kv, ring


#: lengths at the window's edge (32), one short, one past, inside the
#: first page, an inactive slot, and two that have wrapped the ring
_RING_N = (32, 31, 33, 5, 0, 100, 81)
#: a window of 64 over pages of 16 is a ring of 5; read in items of 2
#: (or 4) pages a walk of 5 pages wraps, by the entry it starts at (0
#: to 4, the lengths in that order), nowhere, at an item's edge, inside
#: an item, at an edge, inside the first item; then a second lap, the
#: window's edge, short slots, an inactive slot first and last
_RING_WRAPS = (0, 70, 86, 102, 118, 134, 150, 229, 64, 63, 65, 5, 1, 0)

_RING_CASES = [
    pytest.param(jnp.float32, 2e-5, 32, _RING_N, None, (4, 8),
                 id="float32"),
    pytest.param(jnp.bfloat16, 3e-2, 32, _RING_N, None, (4, 8),
                 id="bfloat16"),
    pytest.param(jnp.float32, 2e-5, 64, _RING_WRAPS, 2, (4, 8),
                 id="ring-of-5-items-of-2"),
    pytest.param(jnp.float32, 2e-5, 64, _RING_WRAPS, 4, (4, 8),
                 id="ring-of-5-items-of-4"),
    pytest.param(jnp.float32, 2e-5, 64, _RING_WRAPS, None, (4, 8),
                 id="ring-of-5-one-item"),
    pytest.param(jnp.float32, 2e-5, 64, (0,) * 4, 2, (4, 8),
                 id="none-live"),
    # 8 KV heads under query groups of 6 and of 8 (48 and 64 heads):
    # one model's two kinds of layer, two shapes of the kernel
    pytest.param(jnp.float32, 2e-5, 64, _RING_WRAPS, None, (8, 48),
                 id="groups-of-6"),
    pytest.param(jnp.float32, 2e-5, 64, _RING_WRAPS, 2, (8, 64),
                 id="groups-of-8"),
    pytest.param(jnp.bfloat16, 3e-2, 32, _RING_N, None, (8, 48),
                 id="groups-of-6-bfloat16"),
]


@pytest.mark.parametrize(
    "dtype,tol,window,lengths,pages_per_chunk,heads", _RING_CASES)
def test_paged_decode_window_over_a_ring(monkeypatch, dtype, tol, window,
                                         lengths, pages_per_chunk, heads):
    """The kernel (interpret mode) over a folded ring pool of 4 (or 8)
    KV heads through ``PagedWindowKV.read`` (the slot's ring as its row
    of the page table, read modulo its length by the kernel; with
    ``pages_per_chunk`` the same call made with items of that many
    pages), against a literal softmax over the last ``window``
    positions: a stale page, the head of the first page and the tail
    of the last are never read (NaN would poison the output)."""
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    rng = np.random.default_rng(3)
    (n_kv, h), d, block = heads, 128, 16
    pool, kv, ring = _ring_case(rng, lengths, n_kv=n_kv, window=window)
    s_ = len(lengths)
    q = jnp.asarray(rng.standard_normal((s_, h, d)), dtype)
    n = np.asarray(lengths)

    class Dims:
        windowed = di.WindowSpec(window, ["window", "window"])
    cache = kv_pager.PagedWindowKV(
        Dims, (jnp.asarray(pool, dtype),), jnp.asarray(n - 1)[:, None],
        jnp.asarray(n > 0)[:, None], (0, 1))
    assert cache.ring == ring == window // block + 1
    assert pk._use_paged_kernel(q, cache.pool)
    if pages_per_chunk is None:
        out = cache.read(q, cache.pool, 1, (n > 0)[:, None], n_kv)
    else:
        out = pk.paged_decode_attention(
            q, cache.pool, 1, cache.pt, jnp.asarray(n, jnp.int32),
            window=window, n_kv=n_kv, pages_per_chunk=pages_per_chunk)
    out = np.asarray(out, np.float32)
    assert not np.isnan(out).any()
    # the fallback reads a ring the same way
    ref = np.asarray(pk._reference_paged_attention(
        q[:, None], (jnp.asarray(np.nan_to_num(pool), dtype),), 1,
        cache.pt, jnp.asarray(n - 1)[:, None], window=window,
        n_kv=n_kv)[:, 0], np.float32)
    assert np.abs(out[n > 0] - ref[n > 0]).max(initial=0.0) < tol
    g = h // n_kv
    for s, length in enumerate(lengths):
        if not length:
            assert (out[s] == 0).all()
            continue
        lo = max(0, length - window)
        keys = np.asarray(jnp.asarray(kv[1, s, lo:length], dtype),
                          np.float64)
        for hh in range(h):
            sc = keys[:, hh // g, :d] @ np.asarray(q[s, hh], np.float64)
            w = np.exp((sc - sc.max()) / np.sqrt(d))
            want = (w / w.sum()) @ keys[:, hh // g, d:]
            assert np.abs(out[s, hh] - want).max() < tol, (s, hh)


@pytest.mark.parametrize("h", [8, 48, 64],
                         ids=["one_head_a_kv_head", "groups_of_6",
                              "groups_of_8"])
@pytest.mark.parametrize("folded", [False, True], ids=["5d", "folded"])
@pytest.mark.parametrize("n_live", [(32, 31, 33, 0, 96, 70),
                                    (0, 96, 1, 17, 96, 0),
                                    (0, 0, 0, 0, 81, 0)],
                         ids=["ragged", "inactive-first-and-last",
                              "one-live"])
def test_paged_decode_window_over_a_plain_page_table(monkeypatch, n_live,
                                                     folded, h):
    """``paged_decode_attention(window=)`` over an ordinary page table
    (every position kept), the pool as ``[L, P, block, Hkv, 2D]`` and
    folded to ``[L, P, block * Hkv, 2D]``: the walk starts at the
    first page that holds a visible key, and pages before it, set to
    NaN here, are not read; the fallback masks the same positions."""
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    rng = np.random.default_rng(5)
    block, mp, n_kv, d = 16, 6, 8, 128
    s_ = len(n_live)
    pool = rng.standard_normal((1, 1 + s_ * mp, block, n_kv, 2 * d))
    pool[:, 0] = np.nan
    pt = 1 + np.arange(s_ * mp, dtype=np.int32).reshape(s_, mp)
    poisoned = pool.copy()
    for s, n in enumerate(n_live):      # pages wholly before the window
        poisoned[:, pt[s, :max(n - 32, 0) // block]] = np.nan
    q = jnp.asarray(rng.standard_normal((s_, h, d)), jnp.float32)
    n = jnp.asarray(n_live, jnp.int32)
    as_stored = ((lambda a: a.reshape(1, -1, block * n_kv, 2 * d))
                 if folded else (lambda a: a))
    extra = {"n_kv": n_kv} if folded else {}
    got = np.asarray(pk.paged_decode_attention(
        q, (jnp.asarray(as_stored(poisoned), jnp.float32),), 0,
        jnp.asarray(pt), n, window=32, pages_per_chunk=2, **extra))
    assert not np.isnan(got).any()
    want = np.asarray(pk._reference_paged_attention(
        q[:, None], (jnp.asarray(np.nan_to_num(pool), jnp.float32),), 0,
        jnp.asarray(pt), (n - 1)[:, None], window=32)[:, 0])
    live = np.asarray(n_live) > 0
    assert np.abs(got[live] - want[live]).max() < 2e-5
    assert (got[~live] == 0).all()
    whole = np.asarray(pk._reference_paged_attention(
        q[:, None], (jnp.asarray(np.nan_to_num(pool), jnp.float32),), 0,
        jnp.asarray(pt), (n - 1)[:, None])[:, 0])
    past = np.asarray(n_live) > 32
    assert np.abs(whole[past] - want[past]).max() > 1e-3
    if (live & ~past).any():
        assert np.abs(whole[live & ~past]
                      - want[live & ~past]).max() < 2e-5


# -- the expert layer's second rule ------------------------------------------

def _moe_params(key, n_held=8, f=64, w=24, n_routed=8):
    ks = jax.random.split(key, 4)
    return {"Wr": jax.random.normal(ks[0], (f, n_routed)) / 8.0,
            "br": jnp.zeros((n_routed,)),
            "Weg": jax.random.normal(ks[1], (n_held, f, w)) / 8.0,
            "Weu": jax.random.normal(ks[2], (n_held, f, w)) / 8.0,
            "Wed": jax.random.normal(ks[3], (n_held, w, f)) / 5.0}


def _reference_layer(p, b, route_rows, faults=()):
    """The benchmark's plain reference over the same rows."""
    from benchmarks.reference import window_moe_lm as ref
    d = dict(top_k=2, eps=1e-6)
    with jax.default_matmul_precision("highest"):
        ids, w, margin = ref.route(
            route_rows @ p["Wr"], top_k=2,
            renorm="no_renorm" not in faults,
            drop="drop_route" in faults)
        chose = ids[:, :, None] == jnp.arange(p["Weg"].shape[0])
        w_e = jnp.sum(w[:, :, None] * chose, axis=1)
        y = jnp.zeros_like(b)
        for e in range(p["Weg"].shape[0]):
            y = ref.expert_rows(
                y, b, jnp.any(chose[:, :, e], axis=1), w_e[:, e],
                p["Weg"][e], p["Weu"][e], p["Wed"][e],
                cap=b.shape[0], act="silu" if "silu" in faults
                else "relu")
    return np.asarray(y), np.asarray(margin)


@pytest.mark.parametrize("rows", [1, 7, 48, 300])
def test_softmax_topk_reglu_layer_against_its_plain_form_and_the_reference(
        rows):
    p = _moe_params(jax.random.PRNGKey(rows))
    b = jax.random.normal(jax.random.PRNGKey(1), (rows, 64))
    a = jax.random.normal(jax.random.PRNGKey(2), (rows, 64))
    y, counts = M.layer(p, b, EXPERTS, route_rows=a)
    plain, plain_counts = M.layer(p, b, EXPERTS, plain=True, route_rows=a)
    np.testing.assert_allclose(y, plain, atol=3e-5)
    np.testing.assert_array_equal(counts, plain_counts)
    assert int(counts.sum()) == 2 * rows       # no pair dropped
    want, margin = _reference_layer(p, b, a)
    assert margin.min() > 1e-5
    np.testing.assert_allclose(y, want, atol=3e-5)
    # the router reads route_rows, the unit is a ReGLU, the weights
    # are renormalised over the chosen: each shows where it is left out
    if rows >= 7:
        for other in (_reference_layer(p, b, b)[0],
                      _reference_layer(p, b, a, ("silu",))[0],
                      _reference_layer(p, b, a, ("no_renorm",))[0],
                      _reference_layer(p, b, a, ("drop_route",))[0]):
            assert np.abs(np.asarray(y) - other).max() > 1e-2


def test_the_shares_of_four_chips_add_up_to_the_whole_layer():
    """The guide's share test under the new rule: 4 chips hold 2
    experts each of 8, none shared. The parts of all 4 shares add up
    to what the uncut reference gives for the layer with all 8."""
    whole = _moe_params(jax.random.PRNGKey(5))
    b = jax.random.normal(jax.random.PRNGKey(6), (48, 64))
    a = jax.random.normal(jax.random.PRNGKey(7), (48, 64))
    want, _ = _reference_layer(whole, b, a)
    total, pairs = 0.0, 0
    for rank in range(4):
        spec = M.ExpertSpec(width=24, n_held=2, n_routed=8, top_k=2,
                            n_shared=0, offset=2 * rank,
                            score="softmax_topk", unit="reglu",
                            route_before_mixer=True)
        mine = dict(whole, **{k: whole[k][2 * rank:2 * rank + 2]
                              for k in ("Weg", "Weu", "Wed")})
        y, counts = M.layer(mine, b, spec, route_rows=a)
        total = total + y
        pairs += int(counts.sum())
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert pairs == 48 * 2      # every pair computed once, somewhere


def _parent_route(h, w_r, bias, *, n_group, topk_group, top_k, scale):
    """``ops.moe.route`` as it stood before ``ExpertSpec`` had a
    ``score`` (PR 45), literally."""
    from jax import lax
    s = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), w_r.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    c = s + bias.astype(jnp.float32)
    t, e = c.shape
    per = e // n_group
    group = lax.top_k(c.reshape(t, n_group, per), 2)[0].sum(-1)
    _, kept = lax.top_k(group, topk_group)
    keep = jnp.any(kept[:, :, None]
                   == jnp.arange(n_group)[None, None, :], axis=1)
    c = jnp.where(jnp.repeat(keep, per, axis=1), c, -jnp.inf)
    _, ids = lax.top_k(c, top_k)
    w = jnp.take_along_axis(s, ids, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return ids.astype(jnp.int32), w


@pytest.mark.parametrize("plain", [False, True], ids=["sorted", "plain"])
def test_the_sigmoid_groups_rule_is_bit_equal_to_the_parent_s(plain):
    """The DeepSeek path's arithmetic does not change by a bit: the
    rule's own outputs, and the layer built on them, against the
    parent's ``route`` kept here literally."""
    spec = M.ExpertSpec(width=32, n_held=4, n_routed=32, top_k=4,
                        n_group=4, topk_group=2, scale=2.5, n_shared=1)
    ks = jax.random.split(jax.random.PRNGKey(9), 8)
    p = {"Wr": jax.random.normal(ks[0], (64, 32)) / 8.0,
         "br": 0.1 * jax.random.normal(ks[1], (32,)),
         "Weg": jax.random.normal(ks[2], (4, 64, 32)) / 8.0,
         "Weu": jax.random.normal(ks[3], (4, 64, 32)) / 8.0,
         "Wed": jax.random.normal(ks[4], (4, 32, 64)) / 5.0,
         "Wsg": jax.random.normal(ks[5], (64, 32)) / 8.0,
         "Wsu": jax.random.normal(ks[6], (64, 32)) / 8.0,
         "Wsd": jax.random.normal(ks[7], (32, 64)) / 5.0}
    h = jax.random.normal(jax.random.PRNGKey(10), (40, 64))
    kw = dict(n_group=4, topk_group=2, top_k=4, scale=2.5)
    ids, w = M.route(h, p["Wr"], p["br"], **kw)
    ids0, w0 = _parent_route(h, p["Wr"], p["br"], **kw)
    np.testing.assert_array_equal(ids, ids0)
    assert np.asarray(w).tobytes() == np.asarray(w0).tobytes()
    y, counts = M.layer(p, h, spec, plain=plain)
    part, counts0 = (M.experts_plain if plain else M.experts)(
        h, p, ids0, w0, (0, 4))
    want = M.gated(h, p["Wsg"], p["Wsu"], p["Wsd"]) + part
    assert np.asarray(y).tobytes() == np.asarray(want).tobytes()
    np.testing.assert_array_equal(counts, counts0)
    assert (spec.score, spec.unit, spec.route_before_mixer) == (
        "sigmoid_groups", "swiglu", False)


# -- the expert kernel against the loop and the plain form -------------------

_KF, _KW = 256, 128         # lane-aligned: the widths the rule takes


def _one_expert(t):
    """Every row's first pair on held expert 2, its second held
    nowhere."""
    return np.stack([np.full(t, 2), np.full(t, -1)], 1)


def _sized(t, sizes):
    """``[t, 2]`` ids whose pairs fill the held experts' groups to
    ``sizes`` exactly, in a scattered order; what is left over is held
    nowhere."""
    flat = np.concatenate([np.full(n, e) for e, n in enumerate(sizes)]
                          + [np.full(2 * t - sum(sizes), -1)])
    return np.random.default_rng(t).permutation(flat).reshape(t, 2)


#: name -> (rows, ids or None for the router's own, live rows or None);
#: most at 32 rows, so that they share their compiled programs
_KERNEL_CASES = {
    "routed_1": (1, None, None),
    "routed_32": (32, None, None),
    "routed_300": (300, None, None),
    # 64 pairs over 4 held experts make 16-row tiles: a group that is
    # empty, one of exactly a tile, one of a tile and a row
    "empty_exact_plus_one": (32, _sized(32, [16, 17, 0, 31]), None),
    "one_row_each": (32, _sized(32, [1, 1, 1, 1]), None),
    "all_on_one_expert": (32, _one_expert(32), None),
    "no_live_row": (32, None, 0),
    "some_live_rows": (32, None, 9),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
@pytest.mark.parametrize("held", [(0, 4), (3, 4)],
                         ids=["all_held", "offset_3_of_8"])
@pytest.mark.parametrize("unit", ["swiglu", "reglu"])
def test_expert_kernel_matches_the_loop_and_the_plain_form(
        monkeypatch, unit, held, case):
    """``ops.moe.experts`` at lane-aligned widths with the kernels
    forced (the Pallas kernel, interpret mode) against the tile loop
    and against every held expert on every row: ``y`` within the
    parity tests' tolerance, ``counts`` the loop's exactly. With
    ``offset`` 3 of 8 published, pairs of experts 0-2 and 7 are held
    nowhere here and add nothing."""
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    rows, ids, live = _KERNEL_CASES[case]
    offset, count = held
    n_routed = 4 if offset == 0 else 8
    p = _moe_params(jax.random.PRNGKey(3), n_held=count, f=_KF, w=_KW,
                    n_routed=n_routed)
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, _KF))
    if ids is None:
        ids, w = M.route(h, p["Wr"], p["br"], n_group=1, topk_group=1,
                         top_k=2, scale=1.0, score="softmax_topk")
    else:
        ids = jnp.asarray(np.where(ids >= 0, ids + offset, -1), jnp.int32)
        w = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(7),
                                             (rows, 2)), axis=-1)
    if live is not None:
        ids = jnp.where((jnp.arange(rows) < live)[:, None], ids, -1)
    assert M._use_expert_kernel(h, p)
    got, counts = jax.jit(M.experts, static_argnums=(4, 5))(
        h, p, ids, w, held, unit)
    loop, loop_counts = jax.jit(M._experts_loop, static_argnums=(4, 5))(
        h, p, ids, w, held, unit)
    plain, plain_counts = jax.jit(M.experts_plain, static_argnums=(4, 5))(
        h, p, ids, w, held, unit)
    np.testing.assert_array_equal(counts, loop_counts)
    np.testing.assert_array_equal(counts, plain_counts)
    np.testing.assert_allclose(got, loop, atol=3e-5)
    np.testing.assert_allclose(got, plain, atol=3e-5)
    assert np.isfinite(np.asarray(got)).all()
    if live == 0:
        assert int(counts.sum()) == 0 and not np.asarray(got).any()
    elif offset == 0 and _KERNEL_CASES[case][1] is None:
        assert int(counts.sum()) == 2 * (rows if live is None else live)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_expert_kernel_through_the_layer_s_blocks(monkeypatch, dtype, tol):
    """A bucket of more than ``ROUTE_BLOCK`` rows goes through
    ``layer``'s ``lax.map`` a block at a time, the kernel inside it,
    with rows of padding that make no pair; in bfloat16 the kernel
    rounds once where the loop rounds three times, and stays within
    bfloat16 (of the largest output) of the float32 plain form."""
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    spec = M.ExpertSpec(width=_KW, n_held=4, n_routed=4, top_k=2,
                        n_shared=0, score="softmax_topk", unit="reglu",
                        route_before_mixer=True)
    p = _moe_params(jax.random.PRNGKey(4), n_held=4, f=_KF, w=_KW,
                    n_routed=4)
    rows = 2 * M.ROUTE_BLOCK
    b = jax.random.normal(jax.random.PRNGKey(5), (rows, _KF))
    a = jax.random.normal(jax.random.PRNGKey(6), (rows, _KF))
    live = jnp.arange(rows) < rows - 700
    cast = lambda tree: jax.tree.map(
        lambda z: z.astype(dtype) if z.ndim == 3 else z, tree)
    got, counts = jax.jit(lambda p, b, a, m: M.layer(
        p, b, spec, live=m, route_rows=a))(cast(p), b.astype(dtype), a,
                                           live)
    want, want_counts = M.layer(p, b, spec, plain=True, live=live,
                                route_rows=a)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == 2 * (rows - 700)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=tol * float(np.abs(want).max()))
    assert got.dtype == dtype and not np.asarray(got[-700:]).any()


def test_the_traced_layers_path_is_tallied(monkeypatch):
    """Which form a traced expert layer took is decided once a
    program, so that is what is counted: ``/metrics``'
    ``dl4j_tpu_moe_expert_layers_traced_total{path=}`` and, with the
    layer's widths and held count, the ``compile/jaxpr_trace`` record
    of the sentried program. At aligned widths with the kernels forced
    it is the kernel; at the toy widths the loop."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.obs import trace
    from deeplearning4j_tpu.perf import sentry
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    tally = lambda: dict(obs.metrics.MOE_EXPERT_LAYERS.snapshot())
    spec = M.ExpertSpec(width=_KW, n_held=4, n_routed=4, top_k=2,
                        n_shared=0, score="softmax_topk", unit="reglu")
    p = _moe_params(jax.random.PRNGKey(4), n_held=4, f=_KF, w=_KW,
                    n_routed=4)
    h = jax.random.normal(jax.random.PRNGKey(5), (12, _KF))
    before, t0 = tally(), trace.now()

    def two_layers(p, h):
        y, _ = M.layer(p, h, spec)
        return M.layer(p, h + y, spec)
    sentry.jit(two_layers, name="test.two_expert_layers")(p, h)
    after = tally()
    assert after.get('{path="kernel"}', 0) - before.get(
        '{path="kernel"}', 0) == 2
    assert after.get('{path="loop"}', 0) == before.get('{path="loop"}', 0)
    said = [r.counts for r in trace.records(t0)
            if r.name == "compile/jaxpr_trace"
            and r.cause == "test.two_expert_layers"
            and r.counts and "expert_layers_kernel" in r.counts]
    assert len(said) == 1, said
    assert {k: said[0][k] for k in ("expert_layers_kernel", "expert_f",
                                    "expert_w", "expert_held")} == {
        "expert_layers_kernel": 2, "expert_f": _KF, "expert_w": _KW,
        "expert_held": 4}
    # the toy widths are not lane-aligned and keep the loop
    toy = _moe_params(jax.random.PRNGKey(1))
    M.layer(toy, jax.random.normal(jax.random.PRNGKey(2), (5, 64)),
            EXPERTS)
    assert tally()['{path="loop"}'] == after.get('{path="loop"}', 0) + 1
    text = obs.metrics.REGISTRY.exposition()
    assert 'dl4j_tpu_moe_expert_layers_traced_total{path="kernel"}' in text


def test_expert_spec_checks_its_rule_and_unit():
    with pytest.raises(ValueError, match="score"):
        M.ExpertSpec(width=8, n_held=4, n_routed=4, top_k=2, score="top")
    with pytest.raises(ValueError, match="unit"):
        M.ExpertSpec(width=8, n_held=4, n_routed=4, top_k=2, unit="gelu")
    assert M.ExpertSpec.of(EXPERTS.to_dict()) == EXPERTS


# -- the pager's two kinds of KV pages ---------------------------------------

class _Owner:
    def __init__(self, tenant="t"):
        self.tenant = tenant


def _pager(slots=3, ctx=128, window=WINDOW, block=BLOCK, n_pages=None):
    spec = di.WindowSpec(window, ["full", "window", "window", "window"])
    return kv_pager.KVPager(
        n_layers=1, n_kv_heads=2, head_dim=24, block=block,
        n_pages=n_pages or 1 + slots * ctx // block, cache_quant=None,
        windowed=(spec, slots))


def _held():
    """``dl4j_tpu_serving_kv_pages{kind}`` as the pager last set it."""
    from deeplearning4j_tpu.obs import metrics
    return {kind: metrics.SERVING_KV_PAGES_HELD.labels(kind=kind).get()
            for kind in ("full", "window")}


def test_pager_holds_two_kinds_of_pages():
    pager = _pager()
    assert pager.ring == 5 and pager.cache is kv_pager.PagedWindowed
    full, window = pager.pool
    assert full.shape == (1, 1 + 3 * 16, BLOCK * 2, 48)
    assert window.shape == (3, 1 + 3 * 5, BLOCK * 2, 48)
    assert pager.pool_bytes() == 4 * (full.size + window.size)
    a, b = _Owner(), _Owner()
    # the gauge by kind: a short sequence holds fewer window pages
    # than its ring, a long one never more
    pages = pager.alloc(pager.pages_for(20), a)
    assert _held() == {"full": 3, "window": 3}
    pager.alloc(pager.pages_for(128), b)
    assert _held() == {"full": 19, "window": 8}
    pager.check_invariants()
    # release returns both kinds
    assert pager.release(a) == len(pages) == 3
    assert _held() == {"full": 16, "window": 5}
    pager.release(b)
    assert _held() == {"full": 0, "window": 0}
    pager.check_invariants()


def test_a_sequence_at_full_context_holds_one_ring_of_a_window_layer():
    """At the cell's sizes: 9,728 positions, window 4,096, block 16.
    What holds a sequence to its ring is the window pool's SHAPE (a
    slot's ring is all the window pages it can name), and that is
    what ``check_invariants`` reads."""
    pager = _pager(slots=1, ctx=9728, window=4096, block=16)
    assert pager.ring == 4096 // 16 + 1 == 257
    assert pager.pool[1].shape[1] == 1 + 257
    pager.alloc(pager.pages_for(9728), _Owner())
    assert _held() == {"full": 608, "window": 257}
    pager.check_invariants()
    # the ring's positions: 9,728 of them over 257 pages, each page
    # written over by the positions 257 * 16 later
    at = (np.arange(9728) // 16) % pager.ring
    assert at.max() == 256 and np.all(at[257 * 16:] == at[:-257 * 16])


def test_invariants_see_a_window_pool_of_another_shape():
    pager = _pager()
    full, window = pager.pool
    pager.pool = (full, window[:, :-1])
    with pytest.raises(kv_pager.PageTableError, match="3 rings of 5"):
        pager.check_invariants()


def test_admission_refuses_by_the_kind_that_is_short(window_lm):
    """The free list (full pages) is the one kind that can be short:
    a slot's ring is its reservation of window pages, so with a slot
    free the window kind never is, and the door has only the full
    pages to hold a request against."""
    model, net = window_lm
    sched = DecodeScheduler(model, net, max_slots=3, block=BLOCK,
                            max_context=128, n_pages=1 + 14)
    assert sched.pages_needed(40, 60) == 13
    assert sched.can_admit(40, 60)
    assert sched.admit(_Req(np.arange(40) % 64, 60))
    assert _held() == {"full": 13, "window": 5}
    # a slot is free, its ring with it; the full kind is short
    assert sched.free_slot() is not None
    assert not sched.can_admit(8, 8)
    assert not sched.admit(_Req(np.arange(8) % 64, 8))
    sched.pager.check_invariants()
    gw = ServingGateway(model, net, max_slots=2, block=BLOCK,
                        max_context=128, n_pages=1 + 6)
    try:
        with pytest.raises(ValueError, match="the pool only has 6"):
            gw.submit(np.arange(40, dtype=np.int32) % 64, max_new=60)
    finally:
        gw.shutdown(drain=False, timeout=30)


@pytest.mark.parametrize("kw,why", [
    ({"prefix_sharing": True}, "shared page"),
    ({"spec_k": 2}, "rejected draft"),
])
def test_scheduler_refuses_what_a_ring_cannot_serve(window_lm, kw, why):
    model, net = window_lm
    with pytest.raises(ValueError, match=why):
        DecodeScheduler(model, net, max_slots=2, block=BLOCK,
                        max_context=128, **kw)


def test_model_arguments_are_checked():
    with pytest.raises(ValueError, match="cache_quant"):
        _model(cache_quant="int8")
    with pytest.raises(ValueError, match="window_layers"):
        CausalTransformerLM(vocab_size=8, hidden=16, n_layers=1,
                            n_heads=2, window_layers=[0])
    with pytest.raises(ValueError, match="softmax"):
        CausalTransformerLM(vocab_size=8, hidden=16, n_layers=1,
                            n_heads=2, window=8, mixer="power_retention")
    model = _model()
    assert model.windowed.kinds == ("full", "window", "window", "window")
    assert [di.layer_theta(model, i) for i in range(4)] == [
        None, 1.5e6, 1.5e6, 1.5e6]
    assert [di.layer_window(model, i) for i in range(4)] == [
        None, 32, 32, 32]
    # the two lists are read each for itself
    other = CausalTransformerLM(vocab_size=8, hidden=16, n_layers=2,
                                n_heads=2, window=8, window_layers=[0],
                                rope_layers=[])
    assert di.layer_window(other, 0) == 8 and di.layer_window(other, 1) \
        is None
    assert di.layer_theta(other, 0) is None


# -- the model's forwards ----------------------------------------------------

def _reference_logits(params, seq, t0, rows, faults=()):
    from benchmarks.reference import window_moe_lm as ref
    with jax.default_matmul_precision("highest"):
        logits, margin = ref.logits_from(
            params, jnp.asarray(seq), t0 - 1, d=ref.dims(_TOY),
            rows=rows, faults=faults)
    return np.asarray(logits), np.asarray(margin)


def test_the_training_forward_equals_the_reference(window_lm):
    """``fit``'s plain forms (the masked einsum, every expert on every
    row) give the reference's logits over a sequence that crosses the
    window."""
    model, net = window_lm
    seq = np.random.default_rng(1).integers(0, 64, 64).astype(np.int32)
    got = np.log(np.asarray(net.output(seq[None], train=False))[0])
    want, _ = _reference_logits(net.params, seq, 1, 64)
    want = want - np.log(np.exp(want).sum(-1, keepdims=True))
    assert np.abs(got - want).max() < 2e-4


def test_fit_trains_the_windowed_expert_model():
    """One step at test size through the plain forms: the loss is
    finite and the router and all three of an expert's matrices move
    (their gradients are not zero)."""
    model = _model()
    net = model.init(seq_len=64)
    before = jax.tree.map(np.asarray, net.params["layer_2"]["moe"])
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, (2, 64)).astype(np.int32)
    net.fit(x, np.roll(x, -1, axis=1))
    assert np.isfinite(float(net.score()))
    after = net.params["layer_2"]["moe"]
    for leaf in ("Wr", "Weg", "Weu", "Wed"):
        assert np.abs(np.asarray(after[leaf]) - before[leaf]).max() > 0, \
            leaf


def test_generate_equals_the_training_forward(window_lm):
    """Dense ``generate()`` keeps every position and masks by the
    window: its greedy tokens are the training forward's, past the
    window."""
    model, net = window_lm
    prompt = np.random.default_rng(4).integers(0, 64, (1, 20)).astype(
        np.int32)
    out = model.generate(net, prompt, 44)
    probs = np.asarray(net.output(out[:, :-1], train=False))[0]
    np.testing.assert_array_equal(probs[19:].argmax(-1), out[0, 20:])


# -- the gateway's path against the plain reference ----------------------

class _Req:
    def __init__(self, prompt, max_new):
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new, self.temperature, self.eos_id = max_new, None, None
        self.tokens, self.done = [], False
        self.tenant = "t"

    def push(self, tok):
        self.tokens.append(int(tok))

    def finish(self):
        self.done = True

    def fail(self, e):
        raise e


def _served_logits(model, net, seq, t0):
    """Teacher-forced logits of ``seq[t0 - 1:]`` by the gateway's own
    programs: the bucket prefill into the sequence's pages and ring,
    then THE paged block a position at a time over the two pools."""
    sched = DecodeScheduler(model, net, max_slots=3, block=BLOCK,
                            max_context=128)
    other = _Req(np.arange(5) % 64, 40)     # ours is not in slot 0
    assert sched.admit(other)
    req = _Req(seq[:t0], len(seq) - t0 + 1)
    assert sched.admit(req)
    slot = next(i for i, s in enumerate(sched._slots)
                if s is not None and s.req is req)

    @jax.jit
    def logits_step(params, pool, pt, lengths, active, prev):
        cache = sched.pager.rows(model, pool, pt, lengths[:, None],
                                 active[:, None])
        x = di.stack(params, prev, model, cache.attend, "test")
        return di.logits(params, x, model, "test"), cache.pool

    params = model.decode_params(net)
    active = np.zeros(3, bool)
    active[slot] = True
    rows = []
    for j, tok in enumerate(seq[t0:]):
        prev, lengths = np.zeros(3, np.int32), np.zeros(3, np.int32)
        prev[slot], lengths[slot] = tok, t0 + j
        logits, pool = logits_step(
            params, sched.pager.pool, jnp.asarray(sched._page_table),
            jnp.asarray(lengths), jnp.asarray(active), jnp.asarray(prev))
        sched.pager.pool = pool
        rows.append(np.asarray(logits[slot], np.float32))
    sched.pager.check_invariants()
    return req.tokens[0], np.stack(rows)


def test_bucket_prefill_by_the_flash_kernel_equals_the_einsum_path(
        window_lm, admits_alike_by_einsum_and_kernel):
    """Window layers (32 keys) beside a full one: a prompt of 33
    tokens in a bucket of 64 rows, the length handed to both kinds."""
    admits_alike_by_einsum_and_kernel(*window_lm, block=BLOCK,
                                      max_context=128)


#: float32 on the CPU, logits up to 3 in size: the bucket prefill and
#: the decode over pages differ from the reference's one pass in the
#: order of float32 sums only (read: 3e-6 at most over the three
#: cases); a window ignored or a rotation on the wrong layer moves a
#: logit by 1e-2 or more, a router rounded to bf16 by 3e-4 where no
#: choice flips and by 1e-2 where one does
LOGIT_TOL = 5e-5


@pytest.mark.parametrize("t0,n_new", [(20, 70), (50, 50), (9, 15)],
                         ids=["crosses_the_window",
                              "prompt_longer_than_the_window",
                              "shorter_than_the_window"])
def test_prefill_then_paged_decode_matches_the_reference_logits(
        window_lm, t0, n_new):
    """A sequence that crosses the window in decode (the ring wraps
    twice), a prompt longer than the window (the bucket prefill keeps
    a window layer's last ring of pages only) and one that never
    reaches it: the pages written at admission and at every decoded
    position are the reference's, by the logits they give."""
    model, net = window_lm
    rng = np.random.default_rng(t0)
    seq = rng.integers(0, 64, t0 + n_new).astype(np.int32)
    first, got = _served_logits(model, net, seq, t0)
    want, margin = _reference_logits(net.params, seq, t0, n_new + 1)
    clear = margin[1:] > 1e-4
    assert clear.sum() >= n_new - 2
    assert first == int(want[0].argmax())
    assert np.abs(got - want[1:])[clear].max() < LOGIT_TOL
    faults = ["rope_on_full", "no_rope_on_window", "bf16_router",
              "post_attention_router", "silu", "no_renorm", "drop_route"]
    if t0 + n_new > WINDOW:
        faults += ["no_window", "window_off_by_one_page"]
    for fault in faults:
        other, _ = _reference_logits(net.params, seq, t0, n_new + 1,
                                     (fault,))
        # (a router rounded to bf16 moves every weight a little even
        # where no choice flips; the others move logits by far more)
        bar = 5 if fault == "bf16_router" else 100
        assert np.abs(got - other[1:]).max() > bar * LOGIT_TOL, fault


def test_bf16_serving_stays_within_bf16_of_the_reference():
    """The same comparison in the compute dtype the cell serves in:
    weights kept as their bf16 rounding (the router in float32), the
    pools in bf16. Away from routing ties the logits lie within a few
    bf16 ulps of a value near 3; read: 0.06 at most."""
    model = _model(compute_dtype="bfloat16")
    net = model.init(seq_len=64)
    net.params = model.decode_params(net)
    assert net.params["layer_1"]["moe"]["Wr"].dtype == jnp.float32
    assert net.params["layer_1"]["moe"]["Weg"].dtype == jnp.bfloat16
    seq = np.random.default_rng(3).integers(0, 64, 80).astype(np.int32)
    _, got = _served_logits(model, net, seq, 23)
    want, margin = _reference_logits(net.params, seq, 23, 58)
    clear = margin[1:] > 0.05
    assert clear.sum() >= 10
    assert np.abs(got - want[1:])[clear].max() < 0.15
    assert np.abs(got - want[1:])[clear].max() > LOGIT_TOL


def test_records_count_the_two_walks(window_lm, monkeypatch):
    """What a step's records say of its page walks, by hand: slots at
    positions 39 and 7 (window 32, block 8; one full and three window
    layers)."""
    model, net = window_lm
    sched = DecodeScheduler(model, net, max_slots=2, block=BLOCK,
                            max_context=128)
    walks = lambda at: sched.pager.cache.step_reads(
        sched.pager, model, np.array(at), sched.max_pages_per_seq)
    got = walks([39, 7])
    # live positions 40 and 8: a window layer reads 32 and 8 of them
    assert got["kv_rows_read"] == 1 * (40 + 8) + 3 * (32 + 8)
    assert got["kv_rows_unwindowed"] == 4 * (40 + 8)
    # window pages: positions 8..39 lie in pages 1..4 (4 pages), and 1
    assert got["kv_pages_window"] == 4 + 1
    assert got["ring_overwrites"] == 0
    # position 40 opens page 5 of a ring of 5: the first overwrite
    assert walks([40])["ring_overwrites"] == 3
    assert walks([41])["ring_overwrites"] == 0


def test_gateway_serves_the_windowed_expert_model(window_lm):
    """Through the public gateway, two tenants: the served tokens are
    dense ``generate()``'s, the step records carry the walks' counts
    and the experts' pairs, and every page of both kinds comes back."""
    from deeplearning4j_tpu import obs
    model, net = window_lm
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, 64, n).astype(np.int32)
               for n in (50, 12, 33)]
    gw = ServingGateway(model, net, max_slots=2, block=BLOCK,
                        max_context=128)
    try:
        gw.warmup(prompt_lens=[len(p) for p in prompts])
        streams = [gw.submit(p, max_new=60, tenant=f"t{i % 2}")
                   for i, p in enumerate(prompts)]
        served = [np.asarray(s.result(timeout=300)) for s in streams]
        pager = gw._sched.pager
        pager.check_invariants()
        assert _held() == {"full": 0, "window": 0}
    finally:
        gw.shutdown(drain=False, timeout=30)
    for p, got in zip(prompts, served):
        want = model.generate(net, p[None], 60)[0]
        np.testing.assert_array_equal(got, want)
    steps = [r for r in obs.trace.records()
             if r.name == "serving.decode_step" and r.counts
             and "kv_rows_read" in r.counts]
    assert steps
    last = [r.counts for r in steps if r.counts.get("expert_pairs")]
    assert all(c["kv_rows_read"] <= c["kv_rows_unwindowed"]
               for c in last)
    assert any(c["kv_rows_read"] < c["kv_rows_unwindowed"] for c in last)
    assert any(c["ring_overwrites"] for c in (r.counts for r in steps))
    # every live row makes top_k pairs in each of the four layers
    assert all(c["expert_pairs"] % (4 * 2) == 0 for c in last)

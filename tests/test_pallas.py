"""Pallas kernel tests (interpret mode on CPU — same code path that
compiles with Mosaic on TPU). Reference coverage: libnd4j
encode_threshold/decode_threshold ops and the attention platform-helper
dispatch (SURVEY §2.1 platform helpers, §3.5 gradient compression)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.nn.layers.attention import (plain_attention,
                                                    scaled_dot_attention)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
# The interpret-mode flash tests became RUNNABLE on this old-jaxlib CI
# env with ISSUE 15's jax.typeof/vma compat fix (they AttributeError'd
# before). The deep backward/variant sweeps cost seconds each in
# interpret mode, and tier-1's 870 s wall-clock budget was already ~96%
# utilised — so the quick parity core stays tier-1 and the heavy
# variants ride the slow lane (still run at round end).
_SLOW = pytest.mark.slow


@pytest.mark.parametrize("causal", [pytest.param(False, marks=_SLOW),
                                    True])
@pytest.mark.parametrize("t", [64, 200])
def test_flash_matches_reference(rng, causal, t):
    B, H, D = 2, 2, 32
    q, k, v = (jnp.asarray(rng.standard_normal((B, t, H, D)),
                           jnp.float32) for _ in range(3))
    ref = scaled_dot_attention(q, k, v, causal=causal)
    out = pk.flash_attention(q, k, v, causal=causal,
                             block_q=64, block_k=64)
    assert float(jnp.max(jnp.abs(ref - out))) < 2e-5


def test_flash_gradients_match_reference(rng):
    B, T, H, D = 1, 96, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                           jnp.float32) for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss(lambda *a, **kw: pk.flash_attention(
        *a, block_q=32, block_k=32, **kw)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(scaled_dot_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


@pytest.mark.parametrize("causal", [pytest.param(False, marks=_SLOW),
                                    True])
@pytest.mark.parametrize("t", [64, 200, 130])
def test_flash_backward_matches_reference(rng, causal, t):
    """The Pallas dQ/dKV kernels (FlashAttention-2 recompute style)
    must agree with autodiff through the einsum reference — including
    ragged lengths that exercise the padded-block masking."""
    B, H, D = 2, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, t, H, D)),
                           jnp.float32) for _ in range(3))
    co = jnp.asarray(rng.standard_normal((B, t, H, D)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) * co)

    g1 = jax.grad(loss(lambda *a, **kw: pk.flash_attention(
        *a, block_q=64, block_k=64, **kw)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(scaled_dot_attention),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


@_SLOW
@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_split_fallback(monkeypatch, rng, causal):
    """Very long sequences fall back from the fused single-pass
    backward to the split dq / dkv kernels (full-length dq scratch
    would exceed VMEM). Force the threshold to 0 so the split path
    stays covered at test sizes."""
    monkeypatch.setattr(pk, "_FUSED_BWD_DQ_VMEM", 0)
    B, H, D, t = 2, 2, 16, 130
    q, k, v = (jnp.asarray(rng.standard_normal((B, t, H, D)),
                           jnp.float32) for _ in range(3))
    co = jnp.asarray(rng.standard_normal((B, t, H, D)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) * co)

    g1 = jax.grad(loss(lambda *a, **kw: pk.flash_attention(
        *a, block_q=64, block_k=64, **kw)), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(scaled_dot_attention),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


@_SLOW
def test_flash_backward_finite_difference(rng):
    """Directional finite-difference check straight through the Pallas
    custom_vjp (float64-free: central difference in f32 with a loose
    tolerance)."""
    B, T, H, D = 1, 40, 1, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                           jnp.float32) * 0.5 for _ in range(3))

    def f(q, k, v):
        return jnp.sum(pk.flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32) ** 2)

    grads = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    key = jax.random.PRNGKey(0)
    eps = 1e-2
    for idx, g in enumerate(grads):
        d = jax.random.normal(key, g.shape, jnp.float32)
        d = d / jnp.linalg.norm(d.reshape(-1))
        args = [q, k, v]
        ap = list(args); ap[idx] = args[idx] + eps * d
        am = list(args); am[idx] = args[idx] - eps * d
        fd = (f(*ap) - f(*am)) / (2 * eps)
        an = jnp.vdot(g, d)
        assert abs(float(fd - an)) < 5e-2 * max(1.0, abs(float(an)))


@_SLOW
def test_flash_backward_bf16(rng):
    """bf16 inputs keep f32 accumulation in the backward kernels."""
    B, T, H, D = 1, 64, 2, 16
    qf, kf, vf = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                              jnp.float32) for _ in range(3))
    q, k, v = (x.astype(jnp.bfloat16) for x in (qf, kf, vf))

    def loss(fn, *a):
        return jnp.sum(fn(*a, causal=False).astype(jnp.float32) ** 2)

    g1 = jax.grad(lambda *a: loss(lambda q, k, v, causal: pk.
                  flash_attention(q, k, v, causal, block_q=32,
                                  block_k=32), *a),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: loss(
        lambda q, k, v, causal: scaled_dot_attention(
            q, k, v, causal=causal), *a), argnums=(0, 1, 2))(qf, kf, vf)
    for a, b in zip(g1, g2):
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))
        assert err < 0.15, err   # bf16 rounding, not accumulation error


@pytest.mark.parametrize("causal", [pytest.param(False, marks=_SLOW),
                                    True])
def test_flash_masked_matches_einsum(rng, causal):
    """Per-example key masks through the Pallas kernel (VERDICT r2 #3):
    padded-batch sequences must match the masked einsum reference —
    forward AND backward, causal and not."""
    B, T, H, D = 3, 96, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)),
                           jnp.float32) for _ in range(3))
    # ragged lengths incl. one full-length row
    lens = jnp.asarray([96, 40, 77])
    mask = (jnp.arange(T)[None, :] < lens[:, None]).astype(jnp.float32)
    co = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * co)

    flash = lambda q, k, v: pk.flash_attention(
        q, k, v, causal=causal, mask=mask, block_q=32, block_k=32)
    ref = lambda q, k, v: scaled_dot_attention(
        q, k, v, mask=mask, causal=causal)
    # only compare valid query rows (masked-out queries differ: flash
    # emits zeros there, einsum emits a uniform average — both are
    # discarded by downstream masking)
    valid = mask[:, :, None, None]
    outf, outr = flash(q, k, v) * valid, ref(q, k, v) * valid
    assert float(jnp.max(jnp.abs(outf - outr))) < 2e-5
    g1 = jax.grad(loss(lambda *a: flash(*a) * valid),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda *a: ref(*a) * valid),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


@_SLOW
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_repeat(rng, causal):
    """Native GQA (kv BlockSpec index map b // groups) must equal
    attention with kv heads explicitly broadcast — fwd AND bwd,
    with a key mask."""
    B, T, H, HKV, D = 2, 96, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, HKV, D)), jnp.float32)
    mask = (jnp.arange(T)[None, :]
            < jnp.asarray([[96], [70]])).astype(jnp.float32)
    co = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    rep = lambda x: jnp.repeat(x, H // HKV, axis=2)

    gqa = lambda q, k, v: pk.flash_attention(
        q, k, v, causal=causal, mask=mask, block_q=32, block_k=32)
    full = lambda q, k, v: pk.flash_attention(
        q, rep(k), rep(v), causal=causal, mask=mask,
        block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(gqa(q, k, v)),
                               np.asarray(full(q, k, v)),
                               rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lambda q, k, v: jnp.sum(gqa(q, k, v) * co),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: jnp.sum(full(q, k, v) * co),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


def test_flash_block_offsets_compose(rng):
    """flash_block_fwd/_merge semantics (the ring-attention surface):
    two half-sequence KV blocks with dynamic global offsets, merged by
    log-sum-exp combination, must equal full causal attention."""
    from deeplearning4j_tpu.parallel.ring_attention import _merge_blocks
    bh, t, d = 2, 64, 16
    q, k, v = (jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
               for _ in range(3))
    half = t // 2
    out = jnp.zeros((bh, half, d), jnp.float32)
    lse = jnp.full((bh, half, 1), -jnp.inf, jnp.float32)
    # queries are the SECOND half (global offset `half`)
    qh = q[:, half:]
    for blk in range(2):
        offs = jnp.asarray([half, blk * half], jnp.int32)
        o_b, lse_b = pk.flash_block_fwd(
            qh, k[:, blk * half:(blk + 1) * half],
            v[:, blk * half:(blk + 1) * half], None, offs, True,
            block_q=32, block_k=32)
        out, lse = _merge_blocks(out, lse, o_b, lse_b)
    want = pk._reference_scan(q, k, v, causal=True, block=32)[:, half:]
    assert float(jnp.max(jnp.abs(out - want))) < 2e-5


def test_flash_block_bwd_composes(rng):
    """flash_block_bwd with global lse: summing per-block dq and
    per-block dk/dv must equal autodiff through full attention."""
    bh, t, d = 2, 64, 16
    q, k, v = (jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
               for _ in range(3))
    co = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    out, lse = pk._flash_fwd(q, k, v, None, None, True, 32, 32,
                             return_lse=True)
    half = t // 2
    dq = jnp.zeros_like(q)
    dks, dvs = [], []
    for blk in range(2):
        sl = slice(blk * half, (blk + 1) * half)
        offs = jnp.asarray([0, blk * half], jnp.int32)
        dq_b, dk_b, dv_b = pk.flash_block_bwd(
            q, k[:, sl], v[:, sl], out, lse, co, None, offs, True,
            block_q=32, block_k=32)
        dq = dq + dq_b
        dks.append(dk_b)
        dvs.append(dv_b)
    dk = jnp.concatenate(dks, axis=1)
    dv = jnp.concatenate(dvs, axis=1)
    want = jax.grad(
        lambda q, k, v: jnp.sum(_dense_causal(q, k, v) * co),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq, dk, dv), want):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


# -- the causal inference forward (a bucket's prefill) -----------------------
_BUCKET, _BQ, _BK = 1024, 128, 256
#: 1, a key block's edge - 1, the edge, the edge + 1, the bucket
_LENGTHS = (1, _BK - 1, _BK, _BK + 1, _BUCKET)


@functools.lru_cache(maxsize=None)
def _prefill_case(window, groups, lanes):
    """Operands of one (window, group, lanes) shape, the jitted kernel
    path and the plain form of them: the lengths are traced, so the
    five of a shape share ONE compilation."""
    rng = np.random.default_rng(groups * 1000 + lanes)
    q, k, v = (jnp.asarray(rng.standard_normal((2, _BUCKET, h, lanes)),
                           jnp.float32) for h in (groups, 1, 1))
    run = jax.jit(lambda n: pk.flash_attention(
        q, k, v, causal=True, window=window, lengths=n, block_q=_BQ,
        block_k=_BK))
    return run, np.asarray(plain_attention(q, k, v, causal=True,
                                           window=window))


@pytest.mark.parametrize("lanes", [128, 192], ids=["128", "192_to_256"])
@pytest.mark.parametrize("groups", [1, 7, 8])
@pytest.mark.parametrize("window", [None, 512, 2 * _BUCKET],
                         ids=["full", "512", "wider_than_the_bucket"])
@pytest.mark.parametrize("n", _LENGTHS)
def test_prefill_path_matches_plain_attention_below_the_length(
        n, window, groups, lanes):
    """The causal inference path (interpret mode; ``block_q`` 128 under
    ``block_k`` 256 and its half, so a q block's edges fall inside a
    key block)
    against the einsum on the rows below each batch row's length, two
    different lengths a call; rows at and past a length come back
    ZERO, whatever their block held."""
    run, want = _prefill_case(window, groups, lanes)
    lengths = (n, _LENGTHS[(_LENGTHS.index(n) + 2) % len(_LENGTHS)])
    got = np.asarray(run(jnp.asarray(lengths, jnp.int32)))
    assert got.shape == want.shape
    for b, nb in enumerate(lengths):
        assert np.abs(got[b, :nb] - want[b, :nb]).max() < 2e-5, (b, nb)
        assert (got[b, nb:] == 0).all(), (b, nb)


def test_prefill_path_takes_a_causal_suffix_and_no_lengths(rng):
    """192 queries against 256 keys (the END-ALIGNED diagonal: a
    static query offset of 64) and a call without lengths (every row
    live: what an evaluation's forward asks for) take the path too."""
    q = jnp.asarray(rng.standard_normal((2, 192, 4, 32)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 256, 2, 32)),
                        jnp.float32) for _ in range(2))
    assert pk._prefill_qualifies(q[0].swapaxes(0, 1), k[0].swapaxes(0, 1),
                                 None, None, True, 64, None, 32, 64)
    for window in (None, 40):
        want = plain_attention(q, k, v, causal=True, window=window)
        got = pk.flash_attention(q, k, v, causal=True, window=window,
                                 block_q=32, block_k=64)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5
        short = pk.flash_attention(
            q, k, v, causal=True, window=window, block_q=32, block_k=64,
            lengths=jnp.asarray([70, 192], jnp.int32))
        assert float(jnp.max(jnp.abs(short[0, :70] - want[0, :70]))) < 2e-5
        assert float(jnp.max(jnp.abs(short[1] - want[1]))) < 2e-5
        assert not np.asarray(short[0, 70:]).any()


def _visible(t, n, window, q_off=0):
    """[t, q_off + t] bool: what query row r < n sees."""
    r = np.arange(t)[:, None] + q_off
    j = np.arange(q_off + t)[None, :]
    see = (j <= r) & (np.arange(t)[:, None] < n)
    return see if window is None else see & (j > r - window)


@pytest.mark.parametrize("bq,half", [(64, 64), (128, 256), (256, 128),
                                     (32, 256), (512, 512)])
@pytest.mark.parametrize("window", [None, 24, 512, 2048],
                         ids=["full", "24", "512", "wider"])
@pytest.mark.parametrize("n", _LENGTHS)
def test_prefill_visits_are_the_blocks_that_hold_a_visible_pair(
        n, window, bq, half):
    """``prefill_visits`` against a brute-force enumeration of the
    (q block, half key block) pairs with at least one visible (query <
    length, key) pair: the wide blocks and the half after them cover
    exactly those, end to end; and ``prefill_pairs`` adds up what the
    tokens see and what the blocks cover."""
    t = _BUCKET
    see = _visible(t, n, window)
    some = see.reshape(t // bq, bq, t // half, half).any(axis=(1, 3))
    done = 0
    for i in range(-(-n // bq)):
        lo, wide, narrow = pk.prefill_visits(i, n, bq, half, window,
                                             xp=pk._Ints)
        assert narrow in (0, 1) and wide >= 0
        assert list(range(lo, lo + 2 * wide + narrow)) \
            == list(np.flatnonzero(some[i])), i
        done += (2 * wide + narrow) * bq * half
    assert not some[-(-n // bq):].any()
    need = int(see.sum())
    assert need == sum(min(r + 1, window or t) for r in range(n))
    blocks = pk._prefill_blocks(t, t, 128, window, None, None)
    if (bq, half) == (blocks[0], blocks[-1]):
        assert pk.prefill_pairs(t, n, window, 128, 2) == (need, done)
    assert pk.prefill_pairs(t, n, window, 128, 2)[0] == need


@pytest.mark.parametrize("window", [None, 96, 4096],
                         ids=["full", "96", "wider"])
@pytest.mark.parametrize("t,bq,bk", [(256, 32, 64), (512, 64, 256)],
                         ids=["its_own_half", "halved"])
def test_prefill_kernel_turns_exactly_the_counted_blocks(rng, t, bq, bk,
                                                         window):
    """The kernel in interpret mode with a counter in its loops: every
    grid step takes exactly the turns ``prefill_visits`` gives its q
    block at its batch row's length, a dead q block none: a whole key
    block a turn, then the half, where a block halves into 128-lane
    tiles; where it is its own half, a turn a block, EVERY one of
    them."""
    b, h, hkv, d = 2, 4, 2, 32
    half = pk._prefill_blocks(t, t, d, window, bq, bk)[-1]
    assert half == (bk if bk % 256 else bk // 2)
    q = jnp.asarray(rng.standard_normal((b * h, t, d)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((b * hkv, t, d)), jnp.float32)
            for _ in range(2))
    lengths = (t // 2 - 31, t)
    _, seen = pk._prefill_fwd(q, k, v, jnp.asarray(lengths, jnp.int32),
                              h // hkv, window, 0, bq, bk,
                              count_visits=True)
    seen = np.asarray(seen).reshape(b, hkv, h // hkv, t // bq)
    turns = 0
    for bi, n in enumerate(lengths):
        for i in range(t // bq):
            _, wide, narrow = (pk.prefill_visits(i, n, bq, half, window,
                                                 xp=pk._Ints)
                               if i * bq < n else (0, 0, 0))
            want = wide + narrow if half != bk else 2 * wide + narrow
            assert (seen[bi, :, :, i] == want).all(), (bi, i)
            turns += want
    assert turns > 0


@pytest.mark.parametrize("window", [None, 200], ids=["full", "200"])
@pytest.mark.parametrize("t,block_k", [(128, None), (512, 128), (1024, 384),
                                       (640, None), (512, 33)])
def test_prefill_path_folds_every_block_where_a_block_is_its_own_half(
        rng, t, block_k, window):
    """A key block that does not halve into whole 128-lane tiles (128,
    384, 640 keys: what a bucket under 1,024 rows gets on the chip; 33
    for an edge inside every block) is walked whole, block after
    block, up to the diagonal: the rows below each length against the
    einsum, zeros past it."""
    bk, *_, half = pk._prefill_blocks(t, t, 32, window, 64, block_k)[1:]
    assert half == bk == (block_k or t)
    q = jnp.asarray(rng.standard_normal((2, t, 4, 32)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, t, 2, 32)), jnp.float32)
            for _ in range(2))
    lengths = (t // 2 + 1, t)
    want = np.asarray(plain_attention(q, k, v, causal=True, window=window))
    got = np.asarray(pk.flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=block_k,
        lengths=jnp.asarray(lengths, jnp.int32)))
    for b, n in enumerate(lengths):
        assert np.abs(got[b, :n] - want[b, :n]).max() < 2e-5, (b, n)
        assert (got[b, n:] == 0).all(), (b, n)


#: sha256[:16] of the lowered text (interpret mode: no source
#: locations) of three calls that do NOT qualify for the causal
#: inference path, as the commit before PR 51 lowered them
_OLD_PATH_TEXT = {
    "lse_asked": "8c1f21cf509b11a4",
    "traced_offsets": "f26b0573d89aa182",
    "kv_past_the_budget": "7c823eebc75c5c6e",
    "kv_past_the_budget_window": "ca0afbe240efebe7",
    "key_mask": "7f5cb0d5d8ec5cfc",
}


def _old_path_call(case):
    sds, f32 = jax.ShapeDtypeStruct, jnp.float32
    x = sds((2, 256, 4, 32), f32)
    kv = sds((2, 256, 2, 32), f32)
    if case == "lse_asked":         # the training forward and backward
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            pk.flash_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2))).lower(x, kv, kv)
    if case == "traced_offsets":    # ring attention's composition
        return jax.jit(lambda q, k, v, o: pk.flash_block_fwd(
            q, k, v, None, o, True)).lower(
                *(sds((8, 256, 32), f32),) * 3, sds((2,), jnp.int32))
    if case == "key_mask":
        return jax.jit(lambda q, k, v, m: pk.flash_attention(
            q, k, v, causal=True, mask=m)).lower(
                x, kv, kv, sds((2, 256), f32))
    big = sds((1, 16384, 2, 128), f32)      # K and V: 16 MiB a head
    window = 4096 if case.endswith("window") else None
    return jax.jit(lambda q, k, v: pk.flash_attention(
        q, k, v, causal=True, window=window)).lower(big, big, big)


@pytest.mark.parametrize("case", sorted(_OLD_PATH_TEXT))
def test_calls_that_do_not_qualify_lower_to_the_text_they_had(case):
    """``lse`` asked, traced offsets, a key mask, K and V past the VMEM
    budget: ``_flash_kernel`` exactly as before the causal inference
    path existed."""
    import hashlib
    text = _old_path_call(case).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _OLD_PATH_TEXT[case]


@_SLOW
def test_flash_block_bwd_kv_longer_than_q(rng):
    """Rectangular kv>q: dk/dv must come back at the KV length, not
    truncated to the q length (regression: dk[:, :t] slice bug)."""
    bh, tq, tk, d = 2, 32, 64, 16
    q = jnp.asarray(rng.standard_normal((bh, tq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, tk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, tk, d)), jnp.float32)
    co = jnp.asarray(rng.standard_normal((bh, tq, d)), jnp.float32)
    out, lse = pk._flash_fwd(q, k, v, None, None, False, 32, 32,
                             return_lse=True)
    dq, dk, dv = pk.flash_block_bwd(q, k, v, out, lse, co,
                                    block_q=32, block_k=32)
    assert dk.shape == k.shape and dv.shape == v.shape

    def ref(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)

    want = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) * co),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b in zip((dq, dk, dv), want):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


def _dense_causal(q, k, v):
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------
def _paged_case(rng, dtype, h, n_kv, d, block, mp, n_live, layers=2):
    """A pool whose live pages hold noise and whose TRASH page (and
    every page no table row names) holds NaN: a kernel that reads one
    of them poisons its output. Unused table entries point at trash,
    as the scheduler leaves them."""
    s_ = len(n_live)
    n_pages = 1 + s_ * mp
    pool = np.full((layers, n_pages, block, n_kv, 2 * d), np.nan,
                   np.float32)
    pt = np.zeros((s_, mp), np.int32)
    for i, n in enumerate(n_live):
        used = -(-n // block)
        pt[i, :used] = 1 + i * mp + np.arange(used)
        pool[:, pt[i, :used]] = rng.standard_normal(
            (layers, used, block, n_kv, 2 * d))
    q = jnp.asarray(rng.standard_normal((s_, h, d)), dtype)
    return (q, jnp.asarray(pool, dtype), jnp.asarray(pt),
            jnp.asarray(n_live, jnp.int32))


# one batch holds the lengths that break page walks: 1, block - 1,
# block, block + 1, an inactive slot, a slot at max_context, ragged
_PAGED_N_LIVE = (1, 15, 16, 17, 0, 96, 33, 70)

# the walk is one pipeline over every (slot, chunk) item of a call (PR
# 48, as the latent kernel's): what a slot's edge, an item's page
# count and the fold's size can get wrong, at 8 pages of 16 a slot and
# chunks of 2, 4 and 8 pages
_PAGED_WALKS = {
    "first-inactive": (0, 40, 128, 5, 64, 33, 1, 17),
    "last-inactive": (40, 128, 5, 64, 33, 1, 17, 0),
    "inactive-between": (33, 0, 0, 64, 0, 1, 0, 128),
    "none-live": (0,) * 8,          # zeros, no copy issued or awaited
    "one-live": (0, 0, 0, 0, 0, 77, 0, 0),
    "one-row": (1,) * 8,
    "one-page": (16,) * 8,
    "whole-chunks": (64, 128, 64, 64, 128, 128, 64, 128),
    "chunk-plus-one-row": (65, 33, 65, 17, 65, 1, 129 - 16, 65),
    # an item's pages are awaited once a set bit of their count
    "page-counts-of-one-bit": (16, 32, 64, 128, 10, 20, 50, 120),
    "page-counts-of-all-bits": (48, 112, 40, 100, 33, 97, 48, 112),
    # a short slot folds a quarter of the buffer a long one filled:
    # the rest is stale, finite, and masked
    "short-after-long": (128, 3, 128, 1, 100, 17, 128, 2),
}

_PAGED_CASES = [
    pytest.param(dtype, tol, h, n_kv, chunk, 6, _PAGED_N_LIVE,
                 id=f"{chunk}-{heads}-{dt}")
    for dtype, tol, dt in ((jnp.float32, 2e-5, "float32"),
                           (jnp.bfloat16, 3e-2, "bfloat16"))
    for h, n_kv, heads in ((32, 8, "gqa4to1"), (8, 8, "mha1to1"))
    for chunk in (2, None)
] + [
    pytest.param(jnp.float32, 2e-5, 32, 8, chunk, 8, n_live,
                 id=f"{chunk}-{name}")
    for name, n_live in _PAGED_WALKS.items() for chunk in (2, 4, None)
]


@pytest.mark.parametrize("dtype,tol,h,n_kv,pages_per_chunk,mp,n_live",
                         _PAGED_CASES)
def test_paged_decode_matches_reference(monkeypatch, rng, dtype, tol,
                                        h, n_kv, pages_per_chunk, mp,
                                        n_live):
    """The kernel (interpret mode) against the registered fallback,
    layer 1 of 2, block 16, ``mp`` pages a slot. (The int8 pool keeps
    the fallback on every platform: ``_use_paged_kernel``.)"""
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    block = 16
    q, pool, pt, n_live = _paged_case(rng, dtype, h, n_kv, 128, block,
                                      mp, n_live)
    assert pk._use_paged_kernel(q, (pool,))
    out = np.asarray(pk.paged_decode_attention(
        q, (pool,), 1, pt, n_live, pages_per_chunk=pages_per_chunk),
        np.float32)
    # the fallback gathers every table entry, trash included, and
    # masks afterwards: give it zeros where the kernel must not look
    clean = jnp.nan_to_num(pool)
    ref = np.asarray(pk._reference_paged_attention(
        q[:, None], (clean,), 1, pt, (n_live - 1)[:, None])[:, 0],
        np.float32)
    live = np.asarray(n_live) > 0
    assert not np.isnan(out).any()          # trash was never read
    assert np.abs(out[live] - ref[live]).max(initial=0.0) < tol
    assert (out[~live] == 0).all()          # inactive: zeros, no walk


@pytest.mark.parametrize("window", [None, 48], ids=["plain", "window"])
def test_paged_decode_never_reads_the_table_past_a_slots_row(
        monkeypatch, rng, window):
    """The copies are issued with the compiler's bounds checks off, so
    the kernel itself holds a walk to the row's length: a length past
    what the row serves reads the row's pages and no entry of the
    next slot's row (whose pages would change the answer)."""
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    q, pool, pt, n_live = _paged_case(rng, jnp.float32, 8, 8, 128, 16, 4,
                                      (64, 64, 64))
    extra = {} if window is None else {"window": window}
    run = lambda n: np.asarray(pk.paged_decode_attention(
        q, (pool,), 0, pt, jnp.asarray(n, jnp.int32), pages_per_chunk=2,
        **extra))
    if window is None:
        want = run((64, 64, 64))
        got = run((500, 64, 9000))
        assert np.abs(got - want).max() < 2e-5
    else:
        # a window of 48 over pages of 16 needs a ring of 4: a row of 4
        # pages read as a ring, at lengths that lap it
        got = run((500, 64, 9000))
        assert not np.isnan(got).any()
        # ... and slot 0 reads ITS pages alone: change slot 1's
        other = pool.at[:, pt[1]].set(7.0)
        again = np.asarray(pk.paged_decode_attention(
            q, (other,), 0, pt, jnp.asarray((500, 64, 9000), jnp.int32),
            pages_per_chunk=2, **extra))
        assert (again[0] == got[0]).all() and (again[2] == got[2]).all()
        assert np.abs(again[1] - got[1]).max() > 1e-3


# ---------------------------------------------------------------------------
# latent decode attention
# ---------------------------------------------------------------------------
def _latent_case(rng, dtype, h, kv_rank, rope, block, mp, n_live,
                 layers=2):
    """As :func:`_paged_case`, for the latent pool: a live row is
    ``[latent | rotary key | zero tail]`` up to whole 128-lane tiles,
    the trash page and every page no table row names hold NaN."""
    s_ = len(n_live)
    width = kv_rank + rope
    stored = -(-width // 128) * 128
    pool = np.full((layers, 1 + s_ * mp, block, stored), np.nan,
                   np.float32)
    pt = np.zeros((s_, mp), np.int32)
    for i, n in enumerate(n_live):
        used = -(-n // block)
        pt[i, :used] = 1 + i * mp + np.arange(used)
        pool[:, pt[i, :used], :, :width] = rng.standard_normal(
            (layers, used, block, width))
        pool[:, pt[i, :used], :, width:] = 0.0
    q = jnp.asarray(rng.standard_normal((s_, h, width)), dtype)
    return (q, jnp.asarray(pool, dtype), jnp.asarray(pt),
            jnp.asarray(n_live, jnp.int32))


# the walk is one pipeline over every (slot, chunk) item of a call:
# what a slot's edge can get wrong, at 2 pages (32 rows) a chunk
_LATENT_N_LIVE = {
    "ragged": _PAGED_N_LIVE,
    "first-inactive": (0, 40, 96, 5, 64, 33, 1, 17),
    "last-inactive": (40, 96, 5, 64, 33, 1, 17, 0),
    "inactive-between": (33, 0, 0, 64, 0, 1, 0, 96),
    "none-live": (0,) * 8,          # zeros, no copy issued or awaited
    "one-row": (1,) * 8,
    "whole-chunks": (32, 64, 96, 32, 32, 64, 96, 96),
    "chunk-plus-one-row": (33, 65, 33, 1, 65, 33, 65, 33),
    # 1, 2 and 3 chunks in turn: the buffer's half flips, or does not,
    # across every kind of slot edge
    "chunks-1-2-3": (20, 50, 90, 30, 60, 96, 10, 40),
}


@pytest.mark.parametrize("n_live", list(_LATENT_N_LIVE.values()),
                         ids=list(_LATENT_N_LIVE))
@pytest.mark.parametrize("pages_per_chunk", [2, None])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["float32", "bfloat16"])
def test_latent_decode_matches_reference(monkeypatch, rng, dtype, tol,
                                         pages_per_chunk, n_live):
    """The kernel (interpret mode) against the registered fallback,
    layer 1 of 2: 16 absorbed query heads over rows of 128 + 64 values
    stored 256 wide, block 16, 6 pages a slot."""
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    q, pool, pt, n_live = _latent_case(rng, dtype, 16, 128, 64, 16, 6,
                                       n_live)
    assert pool.shape[-1] == 256
    padded = jnp.pad(q, ((0, 0), (0, 0), (0, 64)))
    assert pk._use_latent_kernel(padded, pool, 128)
    out = np.asarray(pk.latent_decode_attention(
        q, pool, 1, pt, n_live, 0.11, 128,
        pages_per_chunk=pages_per_chunk), np.float32)
    ref = np.asarray(pk._reference_latent_attention(
        padded, jnp.nan_to_num(pool), 1, pt, n_live, 0.11, 128),
        np.float32)
    live = np.asarray(n_live) > 0
    assert out.shape == (8, 16, 128)
    assert not np.isnan(out).any()          # trash was never read
    assert np.abs(out[live] - ref[live]).max(initial=0.0) < tol
    assert (out[~live] == 0).all() and (ref[~live] == 0).all()


def test_latent_decode_dispatch_line(monkeypatch, rng):
    """The kernel takes a pool in the query's dtype whose stored rows
    and latent are whole 128-lane tiles, on the kernel platform; every
    other shape runs the reference, and says the same."""
    calls = []
    real = pk._latent_decode_call
    monkeypatch.setattr(
        pk, "_latent_decode_call",
        lambda *a, **kw: calls.append(a[0].shape) or real(*a, **kw))

    def run(kv_rank, rope, pool_dtype=jnp.float32):
        q, pool, pt, n_live = _latent_case(rng, jnp.float32, 4, kv_rank,
                                           rope, 8, 3, (5, 0, 24))
        pool = jnp.nan_to_num(pool).astype(pool_dtype)
        out = pk.latent_decode_attention(q, pool, 0, pt, n_live, 0.2,
                                         kv_rank)
        ref = pk._reference_latent_attention(
            jnp.pad(q, ((0, 0), (0, 0),
                        (0, pool.shape[-1] - q.shape[-1]))),
            pool, 0, pt, n_live, 0.2, kv_rank)
        assert out.shape == (3, 4, kv_rank)
        assert float(jnp.abs(out[0] - ref[0]).max()) < 2e-5
        assert float(jnp.abs(out[1]).max()) == 0.0      # inactive
        return len(calls)

    assert run(128, 64) == 0                # CPU: the fallback
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    assert run(128, 64) == 1                # over the line: the kernel
    assert run(256, 32) == 2
    assert run(64, 64) == 2                 # latent under a lane tile
    assert run(128, 64, jnp.bfloat16) == 2  # pool not the query's dtype


def test_paged_decode_dispatch_line(monkeypatch, rng):
    """Which shapes the kernel takes is read off the operands: a head
    of 128 lanes, or of 64 (K and V the halves of one tile), over
    8-row kv tiles in a float pool on the kernel platform; everything
    else runs the reference, and says the same."""
    calls = []
    real = pk._paged_decode_call
    monkeypatch.setattr(
        pk, "_paged_decode_call",
        lambda *a, **kw: calls.append(a[0].shape) or real(*a, **kw))

    def run(h, n_kv, d, quant=False):
        q, pool, pt, n_live = _paged_case(rng, jnp.float32, h, n_kv, d,
                                          8, 3, (5, 0, 24))
        pool = jnp.nan_to_num(pool)
        tup = (pool,)
        if quant:
            tup = (pool.astype(jnp.int8),
                   jnp.ones((2, pool.shape[1], n_kv, 2, 8), jnp.float32))
        out = pk.paged_decode_attention(q, tup, 0, pt, n_live)
        ref = pk._reference_paged_attention(
            q[:, None], tup, 0, pt, (n_live - 1)[:, None])[:, 0]
        assert out.shape == q.shape
        assert float(jnp.abs(out[0] - ref[0]).max()) < 2e-5
        assert float(jnp.abs(out[1]).max()) == 0.0      # inactive
        return len(calls)

    assert run(8, 8, 128) == 0              # CPU: the fallback
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    assert run(8, 8, 128) == 1              # over the line: the kernel
    assert run(16, 8, 128) == 2
    assert run(8, 8, 64) == 3               # half a tile: the packed form
    assert run(8, 8, 32) == 3               # neither a tile nor half
    assert run(12, 12, 128) == 3            # kv heads not whole tiles
    assert run(4, 2, 128) == 3
    assert run(8, 8, 128, quant=True) == 3  # int8 codes and scales


def test_reference_scan_matches_full_attention(rng):
    # the O(T)-memory backward path is itself correct
    bh, t, d = 3, 130, 16
    q, k, v = (jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
               for _ in range(3))
    got = pk._reference_scan(q, k, v, causal=True, block=64)
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    want = jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


# ---------------------------------------------------------------------------
# threshold codec
# ---------------------------------------------------------------------------
def test_threshold_codec_roundtrip(rng):
    g = jnp.asarray(rng.standard_normal(10_001), jnp.float32) * 0.01
    tau = 0.012
    packed, resid = pk.threshold_encode(g, tau)
    dense = pk.threshold_decode(packed, tau, g.size)
    expect = jnp.where(g > tau, tau, jnp.where(g < -tau, -tau, 0.0))
    assert np.allclose(dense, expect)
    assert np.allclose(resid, g - expect, atol=1e-7)
    # 2 bits per element on the wire
    assert packed.size * 4 <= g.size / 2


def test_threshold_codec_2d_shape(rng):
    g = jnp.asarray(rng.standard_normal((37, 53)), jnp.float32) * 0.1
    packed, resid = pk.threshold_encode(g, 0.05)
    dense = pk.threshold_decode(packed, 0.05, g.size, g.shape)
    assert dense.shape == g.shape and resid.shape == g.shape
    assert np.allclose(dense + resid, g, atol=1e-6)


def test_packed_exchange_multidevice(rng):
    """exchange_packed inside shard_map over the 8-device CPU mesh:
    identical result on every device, equals the mean of the decoded
    local updates (reference fan-out semantics)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from deeplearning4j_tpu.parallel.compression import \
        EncodedGradientsAccumulator

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("data",))
    acc = EncodedGradientsAccumulator()
    grads = {"w": jnp.asarray(
        rng.standard_normal((8, 64)), jnp.float32) * 0.01}
    state = acc.init_state({"w": grads["w"][0]})

    def f(g, st):
        return acc.exchange_packed(g, st, axis_name="data")

    out, new_state = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P("data"), P()),
        out_specs=(P("data"), P()),
        check_vma=False))(grads, state)
    # every device got the same averaged update
    got = out["w"]                       # [8, 64] — one row per device
    assert np.allclose(got, got[0:1], atol=1e-6)
    tau = float(state["tau"])
    expect = np.mean([np.where(g > tau, tau,
                               np.where(g < -tau, -tau, 0.0))
                      for g in np.asarray(grads["w"])], axis=0)
    assert np.allclose(got[0], expect, atol=1e-6)


def test_attention_dispatch_uses_einsum_on_cpu(rng):
    # on CPU the helper dispatch must stay on the einsum path (float64
    # gradcheck support) — just exercises the guard
    q = jnp.asarray(rng.standard_normal((1, 1100, 1, 8)), jnp.float32)
    out = scaled_dot_attention(q, q, q)
    assert out.shape == q.shape


def test_flash_dispatch_gate(monkeypatch, rng):
    """Routing gate (VERDICT r3 #6): the flash path is chosen on the
    KEY length — cross-attention (Tq != Tk) and short-query/long-key
    shapes qualify; the threshold comes from DL4J_TPU_FLASH_MIN_T."""
    from deeplearning4j_tpu.nn.layers.attention import _use_flash
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q_tiny = jnp.zeros((1, 8, 2, 16), jnp.float32)
    q_cross = jnp.zeros((1, 256, 2, 16), jnp.float32)
    q_long = jnp.zeros((1, 2048, 2, 16), jnp.float32)
    k_long = jnp.zeros((1, 2048, 2, 16), jnp.float32)
    k_short = jnp.zeros((1, 64, 2, 16), jnp.float32)
    assert _use_flash(q_long, k_long)           # self, long
    assert _use_flash(q_cross, k_long)          # cross, Tq != Tk
    # tiny Tq (scan-step query, learned-query pooling): einsum — the
    # kernel would pad Tq to a 128-row block per launch
    assert not _use_flash(q_tiny, k_long)
    assert not _use_flash(q_long, k_short)      # long q, short keys
    # causal Tq > Tk: the paths define keyless leading rows
    # differently — must stay einsum
    q_xl = jnp.zeros((1, 4096, 2, 16), jnp.float32)
    assert not _use_flash(q_xl, k_long, causal=True)
    assert _use_flash(q_xl, k_long)             # non-causal is fine
    with jax.enable_x64(True):
        assert not _use_flash(jnp.zeros((1, 2048, 2, 16), jnp.float64),
                              k_long)
    monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "32")
    assert _use_flash(q_cross, k_short)         # threshold is a flag
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not _use_flash(q_long, k_long)


def test_flash_dispatch_routes_cross_attention(monkeypatch, rng):
    """scaled_dot_attention actually hands Tq != Tk (and masked
    Ulysses-style full-T masked shapes) to the kernel when the gate
    passes — the pre-round-4 gate required Tq == Tk."""
    import deeplearning4j_tpu.ops.pallas_kernels as pk_mod
    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pk_mod, "flash_attention",
        lambda q, k, v, causal=False, mask=None, **kw:
            calls.append((q.shape[1], k.shape[1], mask is not None))
            or jnp.zeros(q.shape, q.dtype))
    q = jnp.zeros((1, 256, 2, 16), jnp.float32)
    k = jnp.zeros((1, 2048, 2, 16), jnp.float32)
    mask = jnp.ones((1, 2048), jnp.float32)
    scaled_dot_attention(q, k, k, causal=True)            # cross
    scaled_dot_attention(k, k, k, mask=mask)              # masked full-T
    assert calls == [(256, 2048, False), (2048, 2048, True)]


@pytest.mark.parametrize("causal", [pytest.param(False, marks=_SLOW),
                                    True])
def test_flash_cross_attention_matches_einsum(rng, causal):
    """Tq != Tk through the kernel: end-aligned causal diagonal
    (tril(.., Tk - Tq)) and key masks must match the dense path,
    fwd and bwd."""
    B, TQ, TK, H, D = 2, 32, 96, 2, 16
    q = jnp.asarray(rng.standard_normal((B, TQ, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, TK, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, TK, H, D)), jnp.float32)
    mask = (jnp.arange(TK)[None, :]
            < jnp.asarray([[96], [61]])).astype(jnp.float32)
    co = jnp.asarray(rng.standard_normal((B, TQ, H, D)), jnp.float32)
    flash = lambda q, k, v: pk.flash_attention(
        q, k, v, causal=causal, mask=mask, block_q=32, block_k=32)
    ref = lambda q, k, v: scaled_dot_attention(
        q, k, v, mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(ref(q, k, v)),
                               rtol=1e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: jnp.sum(flash(*a) * co),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(ref(*a) * co),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-5


# -- retention decode over the paged state pool ----------------------------

def _retention_case(rng, d, n_slots=4, n_kv=2, groups=3, pages=6,
                    layers=2):
    from deeplearning4j_tpu.ops import retention
    f32 = jnp.float32
    rows = retention.state_rows(d)
    s_pool = jnp.asarray(rng.standard_normal(
        (layers, pages, n_kv, rows, d)), f32)
    z = rng.standard_normal((layers, pages, n_kv, d, d))
    z_pool = jnp.asarray(z + z.swapaxes(-1, -2), f32)
    q = jnp.asarray(rng.standard_normal((n_slots, n_kv * groups, d)), f32)
    k, v = (jnp.asarray(rng.standard_normal((n_slots, n_kv, d)), f32)
            for _ in range(2))
    g = jax.nn.sigmoid(jnp.asarray(
        rng.standard_normal((n_slots, n_kv)) + 3.0, f32))
    return q, k, v, g, (s_pool, z_pool)


_RETENTION_WALKS = {
    "gap": (True, False, True, True), "one": (False, True, False, False),
    "none": (False,) * 4, "all": (True,) * 4,
    "last": (False, False, False, True),
    "first-dead": (False, True, True, True),
    "apart": (True, False, True, False),
    # slots 0 and 1 lie on pages 3 and 2
    "neighbours": (True, True, False, False)}


@pytest.mark.parametrize("d,group,active", [
    pytest.param(d, group, active, id=f"{d}-g{group}-{walk}")
    for d, groups in ((16, (1, 2, 3, 5)), (128, (2,)))
    for group in groups for walk, active in _RETENTION_WALKS.items()
    # the lane-wide head is slow in interpret mode: its first four walks
    if d == 16 or walk in ("gap", "one", "none", "all")])
def test_retention_decode_matches_reference(rng, d, group, active):
    """The kernel (interpret mode) against the registered fallback,
    layer 1 of 2, at every number of (slot, head) items a phase (groups
    that divide the live items and groups that do not): outputs, the
    live slots' pages updated where they lie, and every other page of
    the pool bit for bit (an inactive slot's state is neither read nor
    written, nor is the trash page or any page the step does not
    own)."""
    n_kv, groups = (1, 2) if d == 128 else (2, 3)
    q, k, v, g, pool = _retention_case(rng, d, n_kv=n_kv, groups=groups)
    pages = jnp.asarray([3, 2, 1, 4], jnp.int32)
    act = jnp.asarray(active)
    args = (q.reshape(4, n_kv, groups, d), k, v, g)
    yr, sr, zr = pk._reference_retention_decode(
        *args, pool, 1, pages, act, 1e-6)
    yk, sk, zk = pk._retention_decode_call(
        *args, *pool, jnp.asarray(1, jnp.int32), pages, act, eps=1e-6,
        group=group, interpret=True)
    live = np.asarray(pages)[np.asarray(active)]
    scale = float(jnp.abs(yr).max()) + 1.0
    assert float(jnp.abs(yk - yr).max()) < 2e-5 * scale
    assert float(jnp.abs(yk[~np.asarray(active)]).max(initial=0)) == 0
    for new, ref, old in ((sk, sr, pool[0]), (zk, zr, pool[1])):
        if len(live):
            np.testing.assert_allclose(new[1, live], ref[1, live],
                                       rtol=1e-5, atol=1e-5)
        rest = [p for p in range(6) if p not in set(live.tolist())]
        np.testing.assert_array_equal(new[1, rest], old[1, rest])
        np.testing.assert_array_equal(new[0], old[0])   # other layer


def test_retention_decode_dispatch_line(monkeypatch, rng):
    """A 128-lane head on the kernel platform takes the kernel;
    everything else the reference, with the same answer."""
    calls = []
    real = pk._retention_decode_call
    monkeypatch.setattr(
        pk, "_retention_decode_call",
        lambda *a, **kw: calls.append(a[0].shape) or real(*a, **kw))

    def run(d):
        q, k, v, g, pool = _retention_case(rng, d, n_slots=2, n_kv=1,
                                           groups=2, pages=3)
        pages = jnp.asarray([2, 1], jnp.int32)
        act = jnp.asarray([True, False])
        y, new = pk.retention_decode(q, k, v, g, pool, 0, pages, act)
        yr, sr, _ = pk._reference_retention_decode(
            q.reshape(2, 1, 2, d), k, v, g, pool, 0, pages, act, 1e-6)
        assert y.shape == q.shape
        assert float(jnp.abs(y - yr.reshape(q.shape)).max()) < 1e-3
        np.testing.assert_allclose(new[0][0, 2], sr[0, 2], rtol=1e-5,
                                   atol=1e-5)
        return len(calls)

    assert run(128) == 0                    # CPU: the fallback
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    assert run(128) == 1                    # over the line: the kernel
    assert run(16) == 1                     # head under 128 lanes


def test_a_traced_program_says_the_retention_kernel_s_form(monkeypatch,
                                                           rng):
    """As ``ops.moe.experts`` says which form of an expert layer a
    program holds: the sentried program's ``compile/jaxpr_trace``
    record counts the retention kernel's calls and carries a state's
    bytes, the copies a state and the states of buffers in VMEM."""
    from deeplearning4j_tpu.obs import trace
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.ops import retention
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    q, k, v, g, pool = _retention_case(rng, 128, n_slots=2, n_kv=1,
                                       groups=2, pages=3)
    pages = jnp.asarray([2, 1], jnp.int32)
    act = jnp.asarray([True, False])
    t0 = trace.now()
    sentry.jit(lambda *a: pk.retention_decode(*a[:4], a[4:6], 0, *a[6:]),
               name="test.retention_layer")(q, k, v, g, *pool, pages, act)
    said = [r.counts for r in trace.records(t0)
            if r.name == "compile/jaxpr_trace"
            and r.cause == "test.retention_layer" and r.counts
            and "retention_decode_kernels" in r.counts]
    assert len(said) == 1, said
    rows = retention.state_rows(128)
    assert {k: said[0][k] for k in (
        "retention_decode_kernels", "state_bytes", "state_parts",
        "state_buffers")} == {
            "retention_decode_kernels": 1,
            "state_bytes": 4 * (rows + 128) * 128, "state_parts": 1,
            "state_buffers": 8}

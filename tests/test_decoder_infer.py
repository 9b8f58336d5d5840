"""``nn/decoder_infer.py``: the one inference block over the dense and
the paged cache objects, without a scheduler. What differs between
``generate()`` and the gateway is what a layer's rows write and read
back, so that is what is compared: the same rows through
``decoder_infer.block`` over ``DenseKV`` / ``DenseState`` and over the
pager's ``rows`` object, after the same prompt was prefilled into each
layout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.ops import retention
from deeplearning4j_tpu.serving.kv_pager import KVPager, StateChunk
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM

BLOCK, T0, STEPS, ROWS = 8, 11, 7, 3    # decode crosses into a new page
HD = 16                                 # hidden 64 over 4 heads


def _setup(mixer, cache_quant):
    model = CausalTransformerLM(
        vocab_size=64, hidden=64, n_layers=1, n_heads=4, n_kv_heads=2,
        max_len=64, seed=5, mixer=mixer, cache_quant=cache_quant)
    pblk = jax.tree.map(
        # biases and gains off their initial 0 / 1, so that every term
        # of the block is live
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype),
        model.init().params["layer_1"])
    pager = KVPager(
        n_layers=1, n_kv_heads=model.n_kv_heads, head_dim=HD,
        n_pages=1 + 2 * ROWS * 3, block=BLOCK, cache_quant=cache_quant,
        state_rows=(retention.state_rows(HD)
                    if mixer == "power_retention" else None))
    return model, pblk, pager     # the model is the block's ``dims``


def _prefill(dims, pblk, pager, x, tables):
    """The padded prompts ``x [ROWS, 16, F]`` (``T0`` real rows each)
    into both layouts: -> (block output, dense caches, pool)."""
    pool = pager.pool
    if pager.cache.chunk is None:
        dense = []
        out = di.block(pblk, x, di.causal_prefill(
            dims, lambda li, k, v: dense.append(di.dense_kv(
                k, v, 32, pager.cache_quant is not None))), 0)
        for r in range(ROWS):       # the gateway prefills one sequence
            kv = []
            di.block(pblk, x[r:r + 1], di.causal_prefill(
                dims, lambda li, k, v: kv.append((k, v))), 0)
            pool = pager.cache.write_prompt(
                dims, pool, jnp.asarray(tables[r, :2]), kv)
        return out, dense, pool
    valid = jnp.broadcast_to(jnp.arange(16)[None] < T0, (ROWS, 16))
    rows = di.RetentionRows(
        dims, 0, valid, [retention.zero_state(ROWS, dims.n_kv_heads, HD)])
    out = di.block(pblk, x, rows.attend, 0)
    hist = retention.zero_history(1, 16, dims.n_kv_heads, HD, "float32")
    for r in range(ROWS):
        chunk = StateChunk(dims, pool, hist, jnp.asarray(tables[r, 0]),
                           jnp.asarray(0, jnp.int32), valid[r:r + 1])
        got = di.block(pblk, x[r:r + 1], chunk.attend, 0)
        np.testing.assert_allclose(got[0, :T0], out[r, :T0], atol=1e-5)
        pool = chunk.pool
    return out, rows.caches, pool


@pytest.mark.parametrize("mixer,cache_quant", [
    ("softmax", None), ("softmax", "int8"), ("power_retention", None)],
    ids=["softmax", "softmax-int8", "retention"])
def test_block_over_dense_and_paged_cache_objects_agrees(mixer,
                                                         cache_quant):
    dims, pblk, pager = _setup(mixer, cache_quant)
    owners = [object() for _ in range(ROWS)]
    per_seq = pager.pages_for(32)
    tables = np.zeros((ROWS, per_seq), np.int32)
    pager.alloc(2, object())        # sequences do not start at page 1
    for r, o in enumerate(owners):
        tables[r] = pager.alloc(per_seq, o)
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (T0 + STEPS, ROWS, 64))
    prompt = jnp.zeros((ROWS, 16, 64)).at[:, :T0].set(
        xs[:T0].swapaxes(0, 1))
    _, dense, pool = _prefill(dims, pblk, pager, prompt, tables)
    before = [np.asarray(a) for a in pool]
    act = np.array([True, False, True])     # slot 1 sits this one out
    for t in range(T0, T0 + STEPS):
        kind = di.DenseState if mixer == "power_retention" else di.DenseKV
        d = kind(dims, dense, jnp.asarray(t, jnp.int32))
        want = di.block(pblk, xs[t], d.attend, 0)
        dense = d.caches
        p = pager.rows(dims, pool, jnp.asarray(tables),
                       jnp.full((ROWS, 1), t, jnp.int32),
                       jnp.asarray(act)[:, None])
        got = di.block(pblk, xs[t], p.attend, 0)
        pool = p.pool
        np.testing.assert_allclose(got[act], want[act], atol=1e-5)
    # the inactive slot wrote nothing its pages hold, and no page
    # outside the three reservations but the trash page was touched
    mine = tables[1]
    other = np.setdiff1d(np.arange(1, pager.n_pages), tables.ravel())
    for a, b in zip(before, (np.asarray(a) for a in pool)):
        np.testing.assert_array_equal(a[:, mine], b[:, mine])
        assert not b[:, other].any()

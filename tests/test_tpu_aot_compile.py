"""Ask the TPU's own compiler, without a chip.

The Pallas kernels of the main path run in interpret mode under the
CPU backend every other test uses, and interpret mode accepts what
Mosaic refuses (a block not aligned to the tiling, more VMEM than a
kernel may take). libtpu is installed here and compiles for a chip
that is DESCRIBED and not attached, so each kernel below is lowered
and compiled for a ``v5e:2x2`` device at the widths ``chip_smoke.py``
runs, and must contain its ``tpu_custom_call``.

A compile that passes is not a chip run: nothing executes, so results
and times come from ``chip_smoke.py`` on the chip.

Everything that touches the topology lives in the module-scoped
fixtures (only one process may hold libtpu, and pytest-xdist workers
all import this file): nothing at import time, no child process. The
code under test asks ``jax.default_backend()`` and would take its
interpret branch here, so the fixture pins ``_interpret`` to False in
the two kernel modules for the duration of the module.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from deeplearning4j_tpu.ops import fused_norms, pallas_kernels

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """SingleDeviceSharding on the described chip, with the kernels
    steered onto their Mosaic branch and the persistent compile cache
    off (an entry written for a described device cannot be read back
    without the chip and would warn on the next run)."""
    from jax.experimental.compilation_cache import compilation_cache
    mp = pytest.MonkeyPatch()
    mp.setattr(pallas_kernels, "_interpret", lambda: False)
    mp.setattr(fused_norms, "_interpret", lambda: False)
    # the norm gate also asks jax.default_backend(); the existing
    # force flag takes its kernel branch (interpret already pinned off)
    mp.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    mp.undo()


def _compile(fn, chip, *shapes):
    """Lower ``fn`` from (shape, dtype) pairs placed on the described
    chip, compile it with the TPU compiler, return the HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _flash_fwd_bwd(causal=True, masked=False):
    def loss(q, k, v, *m):
        o = pallas_kernels.flash_attention(
            q, k, v, causal=causal, mask=m[0] if masked else None)
        return o.astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))


# [B, T, H, D] — chip_smoke's GPT step (12×64), the wide-head
# geometry (6×128), the 8k-key case whose fused backward raises
# vmem_limit_bytes, and one masked grouped-query case in f32
FLASH_CASES = {
    "b16_t1024_12x64_bf16": dict(b=16, t=1024, h=12, d=64, dt=BF16),
    "b16_t1024_6x128_bf16": dict(b=16, t=1024, h=6, d=128, dt=BF16),
    "b2_t8192_6x128_bf16": dict(b=2, t=8192, h=6, d=128, dt=BF16),
    "b8_t2048_8x64_gqa2_masked_f32": dict(b=8, t=2048, h=8, d=64,
                                          dt=jnp.float32, h_kv=2,
                                          masked=True),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_attention_fwd_bwd_compiles_for_v5e(chip, case):
    c = FLASH_CASES[case]
    q = ((c["b"], c["t"], c["h"], c["d"]), c["dt"])
    kv = ((c["b"], c["t"], c.get("h_kv", c["h"]), c["d"]), c["dt"])
    shapes = [q, kv, kv]
    if c.get("masked"):
        shapes.append(((c["b"], c["t"]), jnp.float32))
    hlo = _compile(_flash_fwd_bwd(masked=c.get("masked", False)), chip,
                   *shapes)
    # forward + backward (fused, or dQ and dK/dV) kernels
    assert hlo.count("tpu_custom_call") >= 2, case


# the bucket prefill's attention in the three serving cells that admit
# by buckets with differing head shapes: [heads, kv heads, lanes, window]
PREFILL_SHAPES = {
    "smallthinker_full": (28, 4, 128, None),
    "smallthinker_window": (28, 4, 128, 4096),
    "laguna_full": (48, 8, 128, None),
    "laguna_window": (64, 8, 128, 512),
    "deepseek_expanded": (128, 128, 256, None),
}


@pytest.mark.parametrize("bucket", [4096, 8192])
@pytest.mark.parametrize("shape", sorted(PREFILL_SHAPES))
def test_prefill_path_compiles_for_v5e(chip, shape, bucket):
    """The causal inference path (the key loop inside the kernel, K and
    V of a kv head whole in VMEM, the prompt's length prefetched) at
    the serving cells' head shapes and their two largest buckets: ONE
    Mosaic kernel under the registry's scope, within the VMEM limit
    it states (Mosaic refuses a kernel whose scoped VMEM passes it)."""
    h, h_kv, d, window = PREFILL_SHAPES[shape]
    q = jax.ShapeDtypeStruct((1, bucket, h, d), BF16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, bucket, h_kv, d), BF16, sharding=chip)
    n = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    assert pallas_kernels._prefill_fits(bucket, bucket, d, window, 2)
    hlo = jax.jit(lambda q, k, v, n: pallas_kernels.flash_attention(
        q, k, v, causal=True, window=window, lengths=n)).lower(
            q, kv, kv, n).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert len(calls) == 1 and "dl4j.ops.flash_attention" in calls[0]
    stated, used = (int(re.search(
        key + r'":\[\{"memory_space":"1","offset":"0","size":"(\d+)"',
        calls[0]).group(1)) for key in ("scoped_memory_configs",
                                        "used_scoped_memory_configs"))
    assert stated == pallas_kernels._PREFILL_VMEM_BYTES
    assert 0 < used <= stated


@pytest.mark.parametrize("bucket", [128, 384, 640, 896])
def test_prefill_path_compiles_where_a_key_block_is_its_own_half(chip,
                                                                 bucket):
    """A bucket under 1,024 rows (``DL4J_TPU_FLASH_MIN_T`` lowered, a
    direct call) whose ONE key block does not halve into whole
    128-lane tiles: the kernel's single loop of whole blocks compiles
    (``tests/test_pallas.py`` holds its values to the einsum's)."""
    h, h_kv, d, _ = PREFILL_SHAPES["smallthinker_full"]
    assert pallas_kernels._prefill_blocks(
        bucket, bucket, d, None, None, None)[-1] == bucket
    q = jax.ShapeDtypeStruct((1, bucket, h, d), BF16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, bucket, h_kv, d), BF16, sharding=chip)
    n = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    hlo = jax.jit(lambda q, k, v, n: pallas_kernels.flash_attention(
        q, k, v, causal=True, lengths=n)).lower(
            q, kv, kv, n).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 1


def _norm_fwd_bwd(kind):
    if kind == "rms":
        def loss(x, g):
            return fused_norms.rms_norm(x, g).astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1)), 1
    if kind == "add_rms":
        def loss(x, d, g):
            y, s = fused_norms.add_rms_norm(x, d, g)
            return (y.astype(jnp.float32).sum()
                    + s.astype(jnp.float32).sum())
        return jax.value_and_grad(loss, argnums=(0, 1, 2)), 2
    def loss(x, g, b):
        return fused_norms.layer_norm(x, g, b).astype(jnp.float32).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2)), 1


# rows: the b16·t1024 train step, and the gateway's decode step at 1
# and 16 slots (bf16 tiles are 16 rows; _blocks aligns rows to 8)
@pytest.mark.parametrize("kind,rows", [
    ("rms", 16 * 1024), ("add_rms", 16 * 1024), ("layer", 16 * 1024),
    ("rms", 1), ("rms", 16), ("add_rms", 16),
])
def test_fused_norm_fwd_bwd_compiles_for_v5e(chip, kind, rows):
    fn, n_x = _norm_fwd_bwd(kind)
    feats = 768
    shapes = [((rows, feats), BF16)] * n_x
    shapes += [((feats,), jnp.float32)] * (2 if kind == "layer" else 1)
    hlo = _compile(fn, chip, *shapes)
    assert hlo.count("tpu_custom_call") >= 2, (kind, rows)


def test_threshold_codec_compiles_for_v5e(chip):
    n = 1 << 20

    def roundtrip(g, tau):
        packed, resid = pallas_kernels.threshold_encode(g, tau)
        return pallas_kernels.threshold_decode(packed, tau, n), resid

    hlo = _compile(roundtrip, chip, ((n,), jnp.float32),
                   ((), jnp.float32))
    assert hlo.count("tpu_custom_call") >= 2


# the serving cells' gateway (benchmarks/configs/mistral-7b-v0.3-6l:
# 32 slots x 160 pages of 16, 32 heads over 8 kv heads x 128, 6 layers)
_CELL = dict(slots=32, max_pages=160, block=16, heads=32, kv_heads=8,
             head=128, layers=6)


def _cell_pool(chip):
    c = _CELL
    return jax.ShapeDtypeStruct(
        (c["layers"], 1 + c["slots"] * c["max_pages"], c["block"],
         c["kv_heads"], 2 * c["head"]), BF16, sharding=chip)


def _pool_sized_ops(hlo, pool):
    """Opcodes of the instructions whose result is the pool's shape."""
    import re
    shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    return sorted(set(re.findall(
        r"= " + re.escape(shape) + r"\S* ([\w\-]+)\(", hlo)))


def test_paged_decode_attention_compiles_for_v5e(chip):
    c = _CELL
    pool = _cell_pool(chip)

    def attend(q, kv, pt, n_live):
        return pallas_kernels.paged_decode_attention(q, (kv,), 3, pt,
                                                     n_live)

    compiled = jax.jit(attend).lower(
        jax.ShapeDtypeStruct((c["slots"], c["heads"], c["head"]), BF16,
                             sharding=chip),
        pool,
        jax.ShapeDtypeStruct((c["slots"], c["max_pages"]), jnp.int32,
                             sharding=chip),
        jax.ShapeDtypeStruct((c["slots"],), jnp.int32, sharding=chip),
    ).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_decode_attention" in hlo
    # the pool reaches the kernel as it lies: a parameter and a bitcast
    assert _pool_sized_ops(hlo, pool) == ["parameter"], hlo[:2000]
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_serving_decode_step_compiles_for_v5e_without_touching_the_pool(
        chip):
    """The whole ``serving.decode_step`` at the cell's sizes: one
    kernel a layer, lowered ONCE, and nothing of the pool's size but
    the in-place scatters of each layer's new KV row (no gather of the
    pool, no copy into another layout in front of the kernel, no
    temporary: the parent's step held 2.7 GB of gathered contexts)."""
    import re
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.serving import DecodeScheduler
    from deeplearning4j_tpu.zoo import CausalTransformerLM
    c = _CELL
    model = CausalTransformerLM(
        vocab_size=32768, hidden=c["heads"] * c["head"],
        n_layers=c["layers"], n_heads=c["heads"],
        n_kv_heads=c["kv_heads"], max_len=4096, ffn_mult=3.5,
        rope_theta=1e6, tie_embeddings=False,
        updater=upd.Sgd(learning_rate=0.0), compute_dtype="bfloat16",
        seed=1)
    # shapes only: nothing of the 1.6 B parameters is made here, and
    # the scheduler's own pool is two pages (the step takes the pool
    # it is handed)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda: model._cast_decode(model.init().params)))
    sched = DecodeScheduler(
        model, None, max_slots=c["slots"], block=c["block"],
        max_context=c["max_pages"] * c["block"], n_pages=2)
    pool = _cell_pool(chip)
    lowered = sched._step_fn.lower(
        params, (pool,),
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
          for a in sched._step_feed_shapes()))
    funcs = re.findall(r"func\.func private @(\w*paged_decode\w*)",
                       lowered.as_text())
    assert len(funcs) == 1, funcs           # one lowering for 6 layers
    compiled = lowered.compile()
    hlo = compiled.as_text()
    kernels = re.findall(
        r"= \S+ custom-call\([^\n]*tpu_custom_call[^\n]*"
        r"paged_decode_attention", hlo)
    assert len(kernels) == c["layers"], len(kernels)
    # parameter -> scatter fusion a layer -> (bitcast into the kernel)
    assert set(_pool_sized_ops(hlo, pool)) <= {
        "parameter", "fusion", "scatter", "bitcast"}, \
        _pool_sized_ops(hlo, pool)
    for comp in re.split(r"\n(?=\S)", hlo):
        if "bf16[" + ",".join(map(str, pool.shape)) + "]" in comp \
                and not comp.startswith("ENTRY"):
            assert " gather(" not in comp and " copy(" not in comp, \
                comp[:400]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20), mem
    assert mem.alias_size_in_bytes >= 2 * 10 ** 9   # the pool, in place


# -- the retention cell: 24 slots x 6 layers, 8 kv heads of 128 ------------

_RET = dict(slots=24, heads=40, kv_heads=8, head=128, layers=6,
            hidden=5120, vocab=151936, ffn=17408)


def _retention_pool(chip):
    from deeplearning4j_tpu.ops import retention
    c = _RET
    rows = retention.state_rows(c["head"])
    assert rows == 8704
    return tuple(
        jax.ShapeDtypeStruct((c["layers"], 1 + c["slots"], c["kv_heads"],
                              r, c["head"]), jnp.float32, sharding=chip)
        for r in (rows, c["head"]))


def test_retention_decode_compiles_for_v5e(chip):
    """The kernel alone at the cell's sizes: Mosaic takes it (dynamic
    8-row tiles, in-kernel transposes, four groups of two states: 36
    MB of buffers under the kernel's own VMEM limit), and both pool
    arrays go through in place: aliased, no temporary of their size."""
    c = _RET
    pool = _retention_pool(chip)
    f32 = jnp.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def decode(q, k, v, g, s_pool, z_pool, pages, active):
        return pallas_kernels.retention_decode(
            q, k, v, g, (s_pool, z_pool), 3, pages, active)

    compiled = jax.jit(decode, donate_argnums=(4, 5)).lower(
        sds((c["slots"], c["heads"], c["head"]), BF16),
        sds((c["slots"], c["kv_heads"], c["head"]), BF16),
        sds((c["slots"], c["kv_heads"], c["head"]), BF16),
        sds((c["slots"], c["kv_heads"])), *pool,
        sds((c["slots"],), jnp.int32), sds((c["slots"],), jnp.bool_),
    ).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "retention_decode" in hlo
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (4 << 20), mem
    assert mem.alias_size_in_bytes >= 5 * 10 ** 9   # the pool, in place


def _retention_sched(chip):
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.serving import DecodeScheduler
    from deeplearning4j_tpu.zoo import CausalTransformerLM
    c = _RET
    model = CausalTransformerLM(
        vocab_size=c["vocab"], hidden=c["hidden"], n_layers=c["layers"],
        n_heads=c["heads"], n_kv_heads=c["kv_heads"], max_len=8192,
        ffn_mult=c["ffn"] / c["hidden"], rope_theta=1e6,
        tie_embeddings=False, updater=upd.Sgd(learning_rate=0.0),
        compute_dtype="bfloat16", seed=1, mixer="power_retention")
    # bf16 leaves, as the benchmark's builder keeps them: shapes only
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, BF16, sharding=chip),
        jax.eval_shape(lambda: model.init().params))
    sched = DecodeScheduler(model, None, max_slots=c["slots"], block=16,
                            max_context=5120, n_pages=2)
    return sched, params


def test_retention_decode_step_compiles_for_v5e_in_place(chip):
    """The whole ``serving.decode_step`` of the retention cell: one
    kernel a layer, lowered ONCE, the 5.4 GB pool aliased through all
    six, and the step's temporaries a few MB beside 12.5 GB of
    arguments (weights in bf16 alone and the pool)."""
    import re
    c = _RET
    sched, params = _retention_sched(chip)
    pool = _retention_pool(chip)
    lowered = sched._step_fn.lower(
        params, pool,
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
          for a in sched._step_feed_shapes()))
    funcs = re.findall(r"func\.func private @(\w*retention_decode\w*)",
                       lowered.as_text())
    assert len(funcs) == 1, funcs           # one lowering for 6 layers
    compiled = lowered.compile()
    hlo = compiled.as_text()
    # the kernel takes and returns the two pool arrays with their
    # layers, pages and heads end to end: a bitcast each way, and
    # nothing else touches either shape (the norms' kernels under the
    # same scope return one array)
    flat = (pool[0].shape[0] * pool[0].shape[1] * pool[0].shape[2],
            ) + pool[0].shape[3:]
    kernels = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
               and "f32[" + ",".join(map(str, flat)) + "]" in ln.split(
                   " custom-call(")[0]]
    assert len(kernels) == c["layers"], len(kernels)
    for shape, ops in ((pool[0].shape, {"parameter", "get-tuple-element",
                                        "bitcast"}),
                       (flat, {"get-tuple-element", "bitcast"})):
        shape = "f32[" + ",".join(map(str, shape)) + "]"
        touched = set(re.findall(
            r"= " + re.escape(shape) + r"\S* ([\w\-]+)\(", hlo))
        assert touched and touched <= ops, (shape, touched)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20), mem
    assert mem.alias_size_in_bytes >= 5 * 10 ** 9
    assert 12.4e9 < mem.argument_size_in_bytes < 12.6e9, mem


def test_retention_chunk_prefill_compiles_for_v5e_in_place(chip):
    """The one prefill program (512 rows): the sequence's state page
    is read and written back in place (dynamic-update-slices into the
    donated pool, no copy of it), as is the prompt's history, and the
    temporaries of a chunk stay under 1 GB (the chunk's keys expanded
    to the state's rows for the state's update; no query is)."""
    sched, params = _retention_sched(chip)
    pool = _retention_pool(chip)
    i32 = jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    assert sched.prefill_chunk == 512
    history = tuple(sds(a.shape, a.dtype) for a in sched._prefill_hist)
    assert history[0].shape == (6, 5120, 8, 128)
    compiled = sched._chunk_fn.lower(
        params, pool, history, sds((), i32), sds((1, 512), i32),
        sds((), i32), sds((), i32), sds((), jnp.float32),
        sds((), jnp.float32), sds((), i32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * 10 ** 9
    assert mem.temp_size_in_bytes < (1 << 30), mem


def test_softmax_decode_step_holds_nothing_of_the_retention_path(chip):
    """A softmax model's step is lowered from the same stack loop
    (``nn/decoder_infer.py``): it must carry no trace of the retention
    cache object (no kernel, no gate, no state), so that the Mistral cells
    run the step they ran."""
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.serving import DecodeScheduler
    from deeplearning4j_tpu.zoo import CausalTransformerLM
    c = _CELL
    model = CausalTransformerLM(
        vocab_size=32768, hidden=c["heads"] * c["head"], n_layers=2,
        n_heads=c["heads"], n_kv_heads=c["kv_heads"], max_len=4096,
        ffn_mult=3.5, rope_theta=1e6, tie_embeddings=False,
        updater=upd.Sgd(learning_rate=0.0), compute_dtype="bfloat16",
        seed=1)
    assert model.mixer == "softmax"
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(lambda: model._cast_decode(model.init().params)))
    assert "Wgate" not in params["layer_1"]["mha"]
    sched = DecodeScheduler(
        model, None, max_slots=c["slots"], block=c["block"],
        max_context=c["max_pages"] * c["block"], n_pages=2)
    assert not sched.recurrent and sched._chunk_fn is None
    pool = jax.ShapeDtypeStruct(
        (2,) + _cell_pool(chip).shape[1:], BF16, sharding=chip)
    text = sched._step_fn.lower(
        params, (pool,),
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
          for a in sched._step_feed_shapes())).as_text()
    assert "retention" not in text
    assert "paged_decode.block_1" in text or "paged_decode" in text


# -- the latent, mixture-of-experts cell: 64 slots x 5 layers ---------------

def _latent_sched(chip):
    """The scheduler and the shapes of ``deepseekv3.decode-saturated``:
    the configuration as the benchmark's builder reads it, bf16 leaves
    and a float32 router, nothing of the 4.6 B parameters made."""
    import json
    from pathlib import Path
    from benchmarks.models import latent_moe_lm as builder
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.ops.moe import FLOAT32_LEAVES
    from deeplearning4j_tpu.serving import DecodeScheduler
    from deeplearning4j_tpu.zoo import CausalTransformerLM
    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmarks" / "configs"
                      / "deepseek-v3-5l-ep16.json").read_text())
    latent, experts = builder.specs(cfg)
    model = CausalTransformerLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], max_len=8192,
        ffn_mult=cfg["intermediate_size"] / cfg["hidden_size"],
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=False,
        updater=upd.Sgd(learning_rate=0.0), compute_dtype="bfloat16",
        seed=1, mixer="latent", latent=latent, experts=experts)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: model.init().params))
    params = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(
            s.shape, jnp.float32 if getattr(path[-1], "key", None)
            in FLOAT32_LEAVES else BF16, sharding=chip)
        for path, s in flat])
    sched = DecodeScheduler(model, None, max_slots=64, block=16,
                            max_context=6144, n_pages=2)
    pool = jax.ShapeDtypeStruct((5, 1 + 64 * 384, 16, 640), BF16,
                                sharding=chip)
    return sched, params, pool


def test_latent_decode_step_compiles_for_v5e_without_touching_the_pool(
        chip):
    """The whole ``serving.decode_step`` of the latent cell: one
    ``latent_decode_attention`` kernel a layer, lowered ONCE; nothing
    of the pool's size but each layer's in-place scatter of the new
    row (no gather of the pool, no copy in front of the kernel); the
    experts' loop multiplies a slice of the stacked experts where it
    lies; 11.66 GB of arguments (9.15 of weights, 2.52 of pool, whose
    576-wide rows take 640 lanes) and a few MB of temporaries."""
    import re
    sched, params, pool = _latent_sched(chip)
    assert sched.pager.pool[0].shape[2:] == pool.shape[2:]
    lowered = sched._step_fn.lower(
        params, (pool,),
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
          for a in sched._step_feed_shapes()))
    funcs = re.findall(r"func\.func private @(\w*latent_decode\w*)",
                       lowered.as_text())
    assert len(funcs) == 1, funcs           # one lowering for 5 layers
    compiled = lowered.compile()
    hlo = compiled.as_text()
    kernels = re.findall(
        r"= \S+ custom-call\([^\n]*tpu_custom_call[^\n]*"
        r"latent_decode_attention", hlo)
    assert len(kernels) == 5, len(kernels)
    assert set(_pool_sized_ops(hlo, pool)) <= {
        "parameter", "fusion", "scatter", "bitcast"}, \
        _pool_sized_ops(hlo, pool)
    shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    for comp in re.split(r"\n(?=\S)", hlo):
        if shape in comp and not comp.startswith("ENTRY"):
            assert " gather(" not in comp and " copy(" not in comp, \
                comp[:400]
    # four expert layers, one loop each; an expert's matrices reach
    # the matmul as a slice of the stack, never as a copy of their own
    assert len(re.findall(r"= \([^\n]*\) while\(", hlo)) == 4
    assert not re.findall(r"= bf16\[7168,2048\]\S* copy\(", hlo)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20), mem
    assert mem.alias_size_in_bytes >= 2.5e9         # the pool, in place
    assert 11.6e9 < mem.argument_size_in_bytes < 11.7e9, mem


def test_latent_decode_kernel_compiles_alone_for_v5e(chip):
    """The kernel alone at the cell's shapes and its own chunk, so
    Mosaic's verdict on the walk is known before a chip run: the
    scalar scan for the next live slot and the buffer's turn kept in
    SMEM over the grid's steps, pool and buffer indexed by one page
    number, one wait a bit of an item's page count, a fold a quarter
    of the chunk; two halves of 1,024 rows x 640 lanes in VMEM. The
    pool reaches the kernel as the parameter it is: the layers' pages
    end to end are a bitcast."""
    import re
    s_, h, w, kv_rank, mp, block = 64, 128, 640, 512, 384, 16
    pool = jax.ShapeDtypeStruct((5, 1 + s_ * mp, block, w), BF16,
                                sharding=chip)
    chunk = pallas_kernels.latent_chunk_pages(block, mp)
    assert chunk * block == pallas_kernels._LATENT_CHUNK_ROWS

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = pallas_kernels._latent_decode_call.lower(
        sds((s_, h, w), BF16), pool, sds((), jnp.int32),
        sds((s_, mp), jnp.int32), sds((s_,), jnp.int32), scale=0.1147,
        kv_rank=kv_rank, pages_per_chunk=chunk, interpret=False).compile()
    hlo = compiled.as_text()
    assert len(re.findall(
        r"= \S+ custom-call\([^\n]*tpu_custom_call[^\n]*"
        r"latent_decode_attention", hlo)) == 1
    assert _pool_sized_ops(hlo, pool) == ["parameter"], hlo[:2000]
    flat = jax.ShapeDtypeStruct((5 * pool.shape[1], block, w), BF16)
    assert _pool_sized_ops(hlo, flat) == ["bitcast"], hlo[:2000]
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_latent_prefill_bucket_compiles_for_v5e(chip):
    """The 4,096-row bucket, the largest the cell admits: the expanded
    form in blocks of rows and the experts' tiles fit beside weights
    and pool (1.4 GB of temporaries, by the compiler's count)."""
    sched, params, pool = _latent_sched(chip)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    i32, f32 = jnp.int32, jnp.float32
    compiled = sched._admit_fn(4096).lower(
        params, (pool,), sds((256,), i32), sds((1, 4096), i32),
        sds((), i32), sds((), f32), sds((), f32), sds((), i32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2.5e9
    assert mem.temp_size_in_bytes < 2 * 10 ** 9, mem


# -- the hybrid state-space cell (granite4h.chat-saturated) ----------------

_HYB = dict(slots=64, n=128, cols=4096, ssm_layers=36, heads=32,
            kv_heads=8, head=64, attn_layers=4, max_pages=192, block=16)


def _state_sized_ops(hlo, pool):
    """Opcodes of the instructions whose result is the float32 state
    pool's shape."""
    import re
    shape = "f32[" + ",".join(map(str, pool.shape)) + "]"
    return set(re.findall(r"= " + re.escape(shape) + r"\S* ([\w\-]+)\(",
                          hlo))


def test_ssm_decode_compiles_for_v5e(chip):
    """The kernel alone at the cell's sizes: Mosaic takes it (the
    broadcast-and-transpose of ``B`` and ``C``, a slot's rows loaded at
    a sublane only the call knows, two groups of eight states as eight
    column parts each: 34 MB of buffers under the kernel's own VMEM
    limit), and the 4.9 GB pool goes through in place: aliased, no
    temporary of its size."""
    c = _HYB
    f32 = jnp.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pool = sds((c["ssm_layers"], 1 + c["slots"], c["n"], c["cols"]))

    def decode(x, b, cc, delta, a_neg, d_skip, pool, pages, active):
        return pallas_kernels.ssm_decode(x, b, cc, delta, a_neg, d_skip,
                                         pool, 7, pages, active)

    compiled = jax.jit(decode, donate_argnums=(6,)).lower(
        sds((c["slots"], c["cols"]), BF16), sds((c["slots"], c["n"])),
        sds((c["slots"], c["n"])), sds((c["slots"], 64)), sds((64,)),
        sds((64,), BF16), pool, sds((c["slots"],), jnp.int32),
        sds((c["slots"],), jnp.bool_)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "ssm_decode" in hlo
    assert _state_sized_ops(hlo, pool) <= {
        "parameter", "get-tuple-element", "bitcast"}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (8 << 20), mem
    assert mem.alias_size_in_bytes >= 4.9e9      # the pool, in place


def test_paged_decode_compiles_for_v5e_at_heads_of_64(chip):
    """The packed form at the hybrid cell's sizes: 32 query heads of
    64 over 8 kv heads whose K and V share one 128-lane row; one
    kernel, the 1.6 GB pool read in place."""
    c = _HYB
    pool = jax.ShapeDtypeStruct(
        (c["attn_layers"], 1 + c["slots"] * c["max_pages"], c["block"],
         c["kv_heads"], 2 * c["head"]), BF16, sharding=chip)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def attend(q, pool, pt, n_live):
        assert pallas_kernels._use_paged_kernel(q, (pool,))
        return pallas_kernels.paged_decode_attention(q, (pool,), 2, pt,
                                                     n_live)

    compiled = jax.jit(attend).lower(
        sds((c["slots"], c["heads"], c["head"]), BF16), pool,
        sds((c["slots"], c["max_pages"]), jnp.int32),
        sds((c["slots"],), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert _pool_sized_ops(hlo, pool) == ["parameter"], hlo[:2000]
    assert compiled.memory_analysis().temp_size_in_bytes < (4 << 20)


def _hybrid_sched(chip, monkeypatch):
    """The scheduler and the shapes of ``granite4h.chat-saturated``:
    the configuration as the benchmark's builder reads it, bf16
    leaves, nothing of the 3.2 B parameters and nothing of the 6.6 GB
    of pools made (the pager's ``zeros`` are shapes while it is
    built)."""
    import json
    from pathlib import Path
    from benchmarks.models import hybrid_ssm_lm as builder
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.serving import DecodeScheduler, kv_pager
    from deeplearning4j_tpu.zoo import CausalTransformerLM
    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmarks" / "configs"
                      / "granite-4.0-h-micro.json").read_text())
    model = CausalTransformerLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], max_len=4096,
        ffn_mult=cfg["shared_intermediate_size"] / cfg["hidden_size"],
        rope_theta=None, tie_embeddings=True,
        updater=upd.Sgd(learning_rate=0.0), compute_dtype="bfloat16",
        seed=1, mixer="hybrid", hybrid=builder.spec(cfg),
        **builder.scalars(cfg))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, BF16, sharding=chip),
        jax.eval_shape(lambda: model.init().params))
    with monkeypatch.context() as mp:
        mp.setattr(kv_pager.jnp, "zeros",
                   lambda shape, dtype=None: jax.ShapeDtypeStruct(
                       shape, dtype, sharding=chip))
        sched = DecodeScheduler(model, None, max_slots=_HYB["slots"],
                                block=16, max_context=3072)
    return sched, params, sched.pager.pool


def test_hybrid_decode_step_compiles_for_v5e_in_place(chip, monkeypatch):
    """The whole ``serving.decode_step`` of the hybrid cell, 40 layers:
    ONE lowering of each kernel for all the layers of its kind, 36 + 4
    kernel calls, the float32 state pool touched by nothing but them,
    12.97 GB of arguments (weights 6.38, state 4.91, tails 0.06, KV
    1.61) and the step's temporaries under 64 MB beside them."""
    import re
    sched, params, pool = _hybrid_sched(chip, monkeypatch)
    assert [a.shape for a in pool] == [
        (4, 1 + 64 * 192, 16, 8, 128), (36, 65, 128, 4096),
        (36, 65, 3 * 4352)]
    lowered = sched._step_fn.lower(
        params, pool,
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
          for a in sched._step_feed_shapes()))
    text = lowered.as_text()
    for kernel in ("ssm_decode", "paged_decode"):
        funcs = re.findall(r"func\.func private @(\w*" + kernel + r"\w*)",
                           text)
        assert len(funcs) == 1, funcs
    compiled = lowered.compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert sum("ssm_decode" in ln for ln in calls) == 36
    assert sum("paged_decode_attention" in ln for ln in calls) == 4
    touched = _state_sized_ops(hlo, pool[1])
    assert touched and touched <= {"parameter", "get-tuple-element",
                                   "bitcast"}, touched
    mem = compiled.memory_analysis()
    assert 12.9e9 < mem.argument_size_in_bytes < 13.0e9, mem
    assert mem.alias_size_in_bytes >= 6.5e9
    assert mem.temp_size_in_bytes < (64 << 20), mem


# -- the windowed expert cell: 48 slots x 9,728 positions, 8 layers (2
# full + 6 window), 4 kv heads of 128, 64 experts of 768 ------------------

def _windowed_sched(chip, monkeypatch):
    """The scheduler and the shapes of
    ``smallthinker21b.longmix-saturated``: the configuration as the
    benchmark's builder reads it, bf16 leaves and float32 routers,
    nothing of the 4.0 B parameters and nothing of the 4.3 GB of pools
    made."""
    import json
    from pathlib import Path
    from benchmarks.models import window_moe_lm as builder
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.ops.moe import FLOAT32_LEAVES
    from deeplearning4j_tpu.serving import DecodeScheduler, kv_pager
    from deeplearning4j_tpu.zoo import CausalTransformerLM
    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmarks" / "configs"
                      / "smallthinker-21ba3b-8l.json").read_text())
    experts, layers = builder.specs(cfg)
    model = CausalTransformerLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_len=16384, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=False, norm_eps=1e-6,
        updater=upd.Sgd(learning_rate=0.0), compute_dtype="bfloat16",
        seed=1, experts=experts, **layers)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: model.init().params))
    params = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(
            a.shape, jnp.float32 if getattr(path[-1], "key", None)
            in FLOAT32_LEAVES else BF16, sharding=chip)
        for path, a in flat])
    with monkeypatch.context() as mp:
        mp.setattr(kv_pager.jnp, "zeros",
                   lambda shape, dtype=None: jax.ShapeDtypeStruct(
                       shape, dtype, sharding=chip))
        sched = DecodeScheduler(model, None, max_slots=48, block=16,
                                max_context=9728)
    return sched, params, sched.pager.pool


def _pool_ops(hlo, a):
    import re
    shape = "bf16[" + ",".join(map(str, a.shape)) + "]"
    return set(re.findall(r"= " + re.escape(shape) + r"\S* ([\w\-]+)\(",
                          hlo))


def test_windowed_decode_step_compiles_for_v5e_in_place(chip, monkeypatch):
    """The whole ``serving.decode_step`` of the windowed expert cell:
    the page-walk kernel lowered once a KIND (with the window and
    without), 2 + 6 calls over the two FOLDED pools (a page of 4 KV
    heads is the ``[64, 256]`` matrix the kernel reads: no copy of a
    pool in front of it), each layer's new row written in place, 12.28
    GB of arguments (weights 7.94, full pages 1.91, rings 2.43) and the
    step's temporaries under 64 MB beside them."""
    import re
    sched, params, pool = _windowed_sched(chip, monkeypatch)
    assert [a.shape for a in pool] == [
        (2, 1 + 48 * 608, 64, 256), (6, 1 + 48 * 257, 64, 256)]
    lowered = sched._step_fn.lower(
        params, pool,
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
          for a in sched._step_feed_shapes()))
    funcs = re.findall(r"func\.func private @(\w*paged_decode\w*)",
                       lowered.as_text())
    assert len(funcs) == 2, funcs
    compiled = lowered.compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert sum("paged_decode_attention" in ln for ln in calls) == 8
    # the experts' tiles: one pipelined kernel a layer, and no loop
    assert sum("moe_expert_tiles" in ln for ln in calls) == 8
    assert not [ln for ln in hlo.splitlines()
                if " while(" in ln and "moe_experts" in ln]
    for a in pool:
        assert _pool_ops(hlo, a) <= {
            "parameter", "get-tuple-element", "bitcast", "fusion",
            "scatter", "dynamic-update-slice"}, _pool_ops(hlo, a)
    mem = compiled.memory_analysis()
    assert 12.2e9 < mem.argument_size_in_bytes < 12.35e9, mem
    assert mem.alias_size_in_bytes >= 4.3e9     # both pools, in place
    assert mem.temp_size_in_bytes < (64 << 20), mem


@pytest.mark.parametrize("kind", ["window", "full"])
def test_paged_decode_compiles_alone_for_v5e_at_smallthinkers_shapes(
        chip, kind):
    """The page walk ALONE at ``smallthinker21b.longmix-saturated``'s
    shapes: 28 query heads over a FOLDED pool of 32 KB pages (16
    positions x 4 KV heads x 256 lanes), the six window layers' rings
    of 257 pages read with ``window=4096`` and the two full layers'
    rows of 608 without: one custom call, the pool a bitcast of the
    parameter (the layers' pages end to end), and under 1 MiB of
    temporaries (the 4 MB buffer is the kernel's own scratch)."""
    layers, pages, extra = {"window": (6, 257, {"window": 4096}),
                            "full": (2, 608, {})}[kind]
    slots = 48
    pool = jax.ShapeDtypeStruct((layers, 1 + slots * pages, 16 * 4, 256),
                                BF16, sharding=chip)

    def attend(q, kv, pt, n_live):
        assert pallas_kernels._use_paged_kernel(q, (kv,))
        return pallas_kernels.paged_decode_attention(
            q, (kv,), 1, pt, n_live, n_kv=4, **extra)

    compiled = jax.jit(attend).lower(
        jax.ShapeDtypeStruct((slots, 28, 128), BF16, sharding=chip), pool,
        jax.ShapeDtypeStruct((slots, pages), jnp.int32, sharding=chip),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip),
    ).compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_decode_attention" in hlo
    assert _pool_ops(hlo, pool) == {"parameter"}, _pool_ops(hlo, pool)
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_windowed_bucket_prefill_compiles_for_v5e(chip, monkeypatch):
    """The largest bucket (8,192 rows): the windowed flash kernel in
    the six window layers, the unwindowed one in the two full layers,
    both pools written in place (a window layer's last 257 pages
    only), and, with the experts routing 4,096 rows at a time, under
    1.6 GB of temporaries beside the 12.28 GB of arguments: 13.7 of the
    chip's 15.75 GB."""
    sched, params, pool = _windowed_sched(chip, monkeypatch)
    i32 = jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ids = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                       sched.pager.prompt_pages_shapes(8192))
    assert [a.shape for a in ids] == [(512,), (257,), (257,)]
    compiled = sched._admit_fn(8192).lower(
        params, pool, ids, sds((1, 8192), i32), sds((), i32),
        sds((), jnp.float32), sds((), jnp.float32),
        sds((), i32)).compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert sum("attn.window/dl4j.ops.flash_attention" in ln
               for ln in calls) == 6
    assert sum("attn.full/dl4j.ops.flash_attention" in ln
               for ln in calls) == 2
    for a in pool:
        assert " copy(" not in " ".join(
            ln for ln in hlo.splitlines()
            if "bf16[" + ",".join(map(str, a.shape)) + "]" in ln.split(
                " = ")[-1][:60])
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4.3e9
    assert mem.temp_size_in_bytes < 1.6e9, mem


# -- the experts' two forms at the two cells' PUBLISHED widths -------------

def _expert_layer(chip, rows, *, f, w, held, routed, top_k, **rule):
    """``ops.moe.layer`` lowered for the described chip from shapes
    (no weights are made): one chip's expert layer over ``rows``."""
    from deeplearning4j_tpu.ops import moe
    spec = moe.ExpertSpec(width=w, n_held=held, n_routed=routed,
                          top_k=top_k, **rule)

    def sds(shape, dtype=BF16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    p = {"Wr": sds((f, routed), jnp.float32),
         "br": sds((routed,), jnp.float32),
         "Weg": sds((held, f, w)), "Weu": sds((held, f, w)),
         "Wed": sds((held, w, f))}
    if spec.n_shared:
        p.update(Wsg=sds((f, spec.n_shared * w)),
                 Wsu=sds((f, spec.n_shared * w)),
                 Wsd=sds((spec.n_shared * w, f)))
    return jax.jit(lambda p, h, live: moe.layer(p, h, spec, live=live)
                   ).lower(p, sds((rows, f)), sds((rows,), jnp.bool_))


_SMALLTHINKER = dict(f=2560, w=768, held=64, routed=64, top_k=6,
                     n_shared=0, score="softmax_topk", unit="reglu",
                     route_before_mixer=True)
_DEEPSEEK = dict(f=7168, w=2048, held=16, routed=256, top_k=8, n_group=8,
                 topk_group=4, scale=2.5, n_shared=1)


@pytest.mark.parametrize("rows", [48, 64, 4096])
def test_the_rule_on_shapes_parts_the_two_expert_cells(chip, rows):
    """The witness that DeepSeek's programs are untouched and
    SmallThinker's changed, at the widths the pinned toy programs
    cannot show: with the kernels forced, 16 held experts of 88.1 MB
    keep the ``while`` over tiles and hold no call of the kernel; 64
    held experts of 11.8 MB hold the kernel's call and no ``while``."""
    from deeplearning4j_tpu.ops import moe
    small = _expert_layer(chip, rows, **_SMALLTHINKER).as_text()
    assert "moe_expert_tiles" in small and "stablehlo.while" not in small
    big = _expert_layer(chip, rows, **_DEEPSEEK).as_text()
    assert "stablehlo.while" in big and "moe_expert_tiles" not in big
    assert 3 * 2560 * 768 * 2 < moe._EXPERT_MAX_BYTES < 3 * 7168 * 2048 * 2


def test_a_buckets_expert_layer_compiles_for_v5e_beside_its_loop(
        chip, monkeypatch):
    """One 4,096-row block of a SmallThinker bucket through the
    kernel: Mosaic takes an expert's three matrices twice over beside
    a 128-row tile (it fits VMEM), and the layer's temporaries pass
    the loop's by no more than the pairs' rows gathered once (the loop
    gathers a tile at a time) and the rows that pad every group to
    whole tiles, in and out. (In the whole bucket program the kernel
    form holds LESS than the loop's: ``test_windowed_bucket_prefill``'s
    bound.)"""
    from deeplearning4j_tpu.ops import moe
    kernel = _expert_layer(chip, 4096, **_SMALLTHINKER).compile()
    calls = [ln for ln in kernel.as_text().splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    assert sum("moe_expert_tiles" in ln for ln in calls) == 1
    monkeypatch.setattr(moe, "_use_expert_kernel", lambda h, p: False)
    loop = _expert_layer(chip, 4096, **_SMALLTHINKER).compile()
    assert "moe_expert_tiles" not in loop.as_text()
    n, bt = 4096 * 6, moe._kernel_tile_rows(4096 * 6, 64)
    assert bt == 128
    padded = (n + 64 * (bt - 1)) // bt * bt - n
    grew = (kernel.memory_analysis().temp_size_in_bytes
            - loop.memory_analysis().temp_size_in_bytes)
    assert grew <= (n + 2 * padded) * 2560 * 2, (grew, padded)


def test_the_largest_expert_the_rule_takes_compiles_for_v5e(chip):
    """An expert at the rule's edge (three 4,096 x 1,280 bf16 matrices,
    31.5 of the 32 MiB) in a 128-row tile: what the rule lets through,
    Mosaic takes."""
    from deeplearning4j_tpu.ops import moe
    assert 3 * 4096 * 1280 * 2 <= moe._EXPERT_MAX_BYTES
    hlo = _expert_layer(chip, 4096, f=4096, w=1280, held=8, routed=8,
                        top_k=2, n_shared=0, score="softmax_topk",
                        unit="swiglu").compile().as_text()
    assert "moe_expert_tiles" in hlo


# -- laguna-xs.2-5l: one dense and four sparse layers, full layers of 48 heads
# beside window layers of 64 over 8 kv heads of 128, 256 experts of 512 ----

def _gated_sched(chip, monkeypatch):
    """The scheduler and the shapes of ``lagunaxs2.agent-saturated``:
    the configuration as the benchmark's builder reads it, bf16 leaves
    and float32 routers, nothing of the 3.87 B parameters and nothing
    of the 4.74 GB of pools made."""
    import json
    from pathlib import Path
    from benchmarks.models import gated_window_moe_lm as builder
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.ops.moe import FLOAT32_LEAVES
    from deeplearning4j_tpu.serving import DecodeScheduler, kv_pager
    from deeplearning4j_tpu.zoo import CausalTransformerLM
    root = Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmarks" / "configs"
                      / "laguna-xs.2-5l.json").read_text())
    experts, layers = builder.specs(cfg)
    model = CausalTransformerLM(
        vocab_size=cfg["vocab_size"], hidden=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_mult=cfg["intermediate_size"] / cfg["hidden_size"],
        max_len=16384, rope_theta=None, tie_embeddings=False,
        norm_eps=1e-6, updater=upd.Sgd(learning_rate=0.0),
        compute_dtype="bfloat16", seed=1, experts=experts, **layers)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: model.init().params))
    params = jax.tree_util.tree_unflatten(treedef, [
        jax.ShapeDtypeStruct(
            a.shape, jnp.float32 if getattr(path[-1], "key", None)
            in FLOAT32_LEAVES else BF16, sharding=chip)
        for path, a in flat])
    with monkeypatch.context() as mp:
        mp.setattr(kv_pager.jnp, "zeros",
                   lambda shape, dtype=None: jax.ShapeDtypeStruct(
                       shape, dtype, sharding=chip))
        sched = DecodeScheduler(model, None, max_slots=48, block=16,
                                max_context=11264)
    return sched, params, sched.pager.pool


def test_gated_windowed_decode_step_compiles_for_v5e_in_place(
        chip, monkeypatch):
    """The whole ``serving.decode_step`` of the gated windowed cell:
    the page walk lowered once a KIND, and the kinds now differ in
    their QUERY GROUP too (6 heads a KV head without a window in the 2
    full layers, 8 with ``window=512`` over a ring of 33 pages in the
    3 window layers), 5 calls over the two folded pools; the experts'
    tiles one pipelined kernel in each of the 4 sparse layers (none in
    the dense one); each layer's new row written in place; 12.48 GB of
    arguments (weights 7.74, full pages 4.43, rings 0.31) and the
    step's temporaries under 96 MB beside them."""
    import re
    sched, params, pool = _gated_sched(chip, monkeypatch)
    assert [a.shape for a in pool] == [
        (2, 1 + 48 * 704, 128, 256), (3, 1 + 48 * 33, 128, 256)]
    lowered = sched._step_fn.lower(
        params, pool,
        *(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)
          for a in sched._step_feed_shapes()))
    funcs = re.findall(r"func\.func private @(\w*paged_decode\w*)",
                       lowered.as_text())
    assert len(funcs) == 2, funcs
    compiled = lowered.compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    walks = [ln for ln in calls if "paged_decode_attention" in ln]
    assert sum("attn.full" in ln for ln in walks) == 2
    assert sum("attn.window" in ln for ln in walks) == 3
    assert sum("moe_expert_tiles" in ln for ln in calls) == 4
    assert not [ln for ln in hlo.splitlines()
                if " while(" in ln and "moe_experts" in ln]
    for a in pool:
        assert _pool_ops(hlo, a) <= {
            "parameter", "get-tuple-element", "bitcast", "fusion",
            "scatter", "dynamic-update-slice"}, _pool_ops(hlo, a)
    # the gate: a [48, H] sigmoid and a product in the compute dtype;
    # what the step WRITES under its scope is bf16 (a v5e multiplies
    # bf16 in float32 inside a fusion: registers, not a copy)
    gated = [ln.split(" = ")[1] for ln in hlo.splitlines()
             if "attn.gate" in ln and " fusion(" in ln
             and ln.startswith("  %")]
    assert len(gated) >= 10 and all(g.startswith("bf16[48,")
                                    for g in gated), gated
    # cos and sin once a RULE, not once a layer
    assert hlo.count(" cosine(") == 2 and hlo.count(" sine(") == 2
    mem = compiled.memory_analysis()
    assert 12.4e9 < mem.argument_size_in_bytes < 12.56e9, mem
    assert mem.alias_size_in_bytes >= 4.7e9     # both pools, in place
    assert mem.temp_size_in_bytes < (96 << 20), mem


def test_gated_windowed_bucket_prefill_compiles_for_v5e(chip, monkeypatch):
    """The largest bucket (8,192 rows): the windowed flash kernel
    (``window=512``, narrower than a KV block) over 64 heads in the
    three window layers, the unwindowed one over 48 heads in the two
    full layers, both pools written in place (a window layer's last 33
    pages only) and, with the experts routing 4,096 rows at a time,
    1.30 GB of temporaries beside the 12.49 GB of arguments: 13.79 of
    the chip's 15.75 GB."""
    sched, params, pool = _gated_sched(chip, monkeypatch)
    i32 = jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ids = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                       sched.pager.prompt_pages_shapes(8192))
    assert [a.shape for a in ids] == [(512,), (33,), (33,)]
    compiled = sched._admit_fn(8192).lower(
        params, pool, ids, sds((1, 8192), i32), sds((), i32),
        sds((), jnp.float32), sds((), jnp.float32),
        sds((), i32)).compile()
    hlo = compiled.as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln
             and " custom-call(" in ln]
    assert sum("attn.window/dl4j.ops.flash_attention" in ln
               for ln in calls) == 3
    assert sum("attn.full/dl4j.ops.flash_attention" in ln
               for ln in calls) == 2
    assert sum("moe_expert_tiles" in ln for ln in calls) == 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 4.7e9
    # 12.49 GB of arguments and 1.30 GB of temporaries: 13.79 GB
    assert 12.4e9 < mem.argument_size_in_bytes < 12.56e9, mem
    assert mem.temp_size_in_bytes < 1.5e9, mem

"""Collective-volume CI gates: the wire table
(`python -m tools.collective_volume --markdown`) is enforced, not just
documented. Each gate compiles a
representative distributed step on the virtual 8-device mesh, parses
the optimized HLO with ``tools.collective_volume``, and asserts the
collective kinds + byte volumes against the ring-algorithm formulas —
a sharding regression (lost allreduce, extra all-gather, mask tensor
rejoining the ring) fails the suite instead of silently drifting a doc.

Reference analog: there is none — the reference never gates wire
volume; this enforces BASELINE #5's "linear to 32 chips" derisking.
"""
import importlib.util
import pathlib
import sys

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")

_TOOLS = pathlib.Path(__file__).resolve().parent.parent / "tools"


def _load_cv():
    spec = importlib.util.spec_from_file_location(
        "collective_volume", _TOOLS / "collective_volume.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules.setdefault("collective_volume", mod)
    spec.loader.exec_module(mod)
    return mod

@pytest.fixture(scope="module")
def cv():
    return _load_cv()


def _volumes(cv, jitted, args, kind):
    colls = cv.collectives_of(jitted.lower(*args).compile())
    return [w for k, _, w in colls if k == kind], colls


def test_dp_resnet_allreduce_matches_ring_formula(cv):
    """DP ResNet-50: ONE gradient-sync all-reduce family whose total
    wire volume equals 2·P·(n−1)/n — the minimal ring volume for the
    fp32 gradient bytes P (BASELINE #5 reading)."""
    jitted, args = cv.dp_resnet()
    ar, colls = _volumes(cv, jitted, args, "all-reduce")
    assert ar, "gradient all-reduce disappeared from the DP step"
    params = args[0]
    p_bytes = sum(np.prod(p.shape) * p.dtype.itemsize
                  for p in jax.tree.leaves(params))
    want = 2 * p_bytes * 7 / 8
    got = sum(ar)
    # small non-gradient allreduces (loss mean, BN stats) ride along;
    # the gradient sync must dominate and not exceed the formula by
    # more than a few percent
    assert want * 0.98 < got < want * 1.05, (got, want)
    # and nothing else moves: a DP step has no business all-gathering
    other = [k for k, _, _ in colls
             if k in ("all-gather", "reduce-scatter", "all-to-all")]
    assert not other, other


def test_dp_broken_sharding_is_caught(cv):
    """Canary: the same step with the batch REPLICATED (a classic
    sharding regression — every device computes the full batch) emits
    no gradient all-reduce, so the formula gate above would fail.
    Proves the gate detects the regression class it exists for."""
    broken, args = cv.dp_resnet(sharded=False)
    ar, _ = _volumes(cv, broken, args, "all-reduce")
    assert sum(ar) < 1e6   # ~0: the gradient sync is gone


def test_zero_dp_reduce_scatter_allgather_and_footprint(cv):
    """ZeRO-DP sharded weight update (ISSUE 6): the compiled sharded
    SYNC step moves gradients by reduce-scatter and params by
    all-gather — at the per-shard/full-tensor byte volumes the flat
    layout implies — and the resident optimizer state drops to ~1/N
    of the replicated footprint per device."""
    jitted, args, acct = cv.dp_sharded_wrapper()
    colls = cv.collectives_of(jitted.lower(*args).compile())
    rs = [(nb, w) for k, nb, w in colls if k == "reduce-scatter"]
    ag = [(nb, w) for k, nb, w in colls if k == "all-gather"]
    assert rs, "sharded step lost its gradient reduce-scatter"
    assert ag, "sharded step lost its param all-gather"
    n = 8
    # reduce-scatter results are the per-device grad shards: total
    # ≈ grad_bytes/n (pad slack allowed); all-gather results are the
    # full flat params: total ≈ param_bytes (plus the small loss mean)
    got_rs = sum(nb for nb, _ in rs)
    assert acct["grad_bytes"] / n * 0.95 < got_rs \
        < acct["grad_bytes"] / n * 1.2, (got_rs, acct)
    got_ag = sum(nb for nb, _ in ag)
    assert acct["param_bytes"] * 0.95 < got_ag \
        < acct["param_bytes"] * 1.2, (got_ag, acct)
    # optimizer-state residency: ~1/N of replicated (adam: 2 moment
    # trees + scalar counts)
    ratio = acct["opt_bytes_per_device"] \
        / acct["opt_bytes_replicated_per_device"]
    assert 1 / n * 0.8 < ratio < 1 / n * 1.6, acct
    # and no dense gradient allreduce remains (scatter replaced it)
    ar = [nb for k, nb, _ in colls if k == "all-reduce"]
    assert sum(ar) < acct["grad_bytes"] * 0.05, ar


def test_zero_dp_replicated_baseline_has_no_scatter(cv):
    """Canary for the gate above: the SAME wrapper step with
    ``sharded_update=False`` emits NO reduce-scatter/all-gather — the
    gradient sync is one fused all-reduce and the optimizer state
    stays replicated (ratio 1)."""
    jitted, args, acct = cv.dp_sharded_wrapper(sharded_update=False)
    colls = cv.collectives_of(jitted.lower(*args).compile())
    other = [k for k, _, _ in colls
             if k in ("reduce-scatter", "all-gather")]
    assert not other, other
    ar = [nb for k, nb, _ in colls if k == "all-reduce"]
    assert sum(ar) > acct["grad_bytes"] * 0.95
    assert acct["opt_bytes_per_device"] \
        == acct["opt_bytes_replicated_per_device"]


def test_tp_mlp_activation_allreduce_only(cv):
    """TP col→row MLP: activations (not params) allreduce — volume is
    activation-sized (≪ param bytes), and no collective-permute."""
    jitted, args = cv.tp_mlp()
    ar, colls = _volumes(cv, jitted, args, "all-reduce")
    assert ar
    params, x = args
    p_bytes = sum(np.prod(p.shape) * p.dtype.itemsize
                  for p in jax.tree.leaves(params))
    act_bytes = np.prod(x.shape) * x.dtype.itemsize
    got = sum(ar)
    # well under even 10% of a param sync; within 8x of one activation
    # allreduce (fwd+bwd, dtype promotion allowed)
    assert got < 0.1 * p_bytes
    assert got <= 8 * 2 * act_bytes * 7 / 8, (got, act_bytes)
    assert not [k for k, _, _ in colls if k == "collective-permute"]


def test_sp_ring_volume_and_no_mask_tensor(cv):
    """SP causal ring fwd+bwd at T=8k: KV blocks + gradient
    accumulators ride collective-permute for n trips; with no key mask
    given, NO mask tensor rotates (round 4's km=None threading) — the
    volume stays within the k/v/dk/dv formula."""
    jitted, args = cv.sp_ring()
    cp, colls = _volumes(cv, jitted, args, "collective-permute")
    assert cp, "ring lost its collective-permutes"
    (q,) = args
    b, t, h, d = q.shape
    n = 8
    shard_bf16 = b * (t // n) * h * d * 2
    shard_f32 = 2 * shard_bf16
    # fwd: k+v; bwd: k+v + dk+dv accumulators (f32); each rotates once
    # per ring trip × n trips. XLA:CPU promotes bf16 buffers to f32,
    # so the band spans bf16-preserved (TPU) .. all-f32 (CPU).
    want_lo = n * (4 * shard_bf16 + 2 * shard_f32)
    want_hi = n * 6 * shard_f32
    got = sum(cp)
    assert want_lo * 0.85 < got < want_hi * 1.1, \
        (got, want_lo, want_hi)


def test_sp_ring_masked_adds_only_mask_bytes(cv):
    """With a key mask the ring carries ONE extra small tensor: volume
    grows by ≈ n·(mask shard bytes)·trips and nothing else."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.ring_attention import \
        ring_self_attention
    mesh = make_mesh({"seq": 8})
    b, t, h, d = 1, 8192, 8, 128
    q = jnp.zeros((b, t, h, d), jnp.bfloat16)
    mask = jnp.ones((b, t), jnp.float32)

    def loss(q):
        return jnp.sum(ring_self_attention(
            q, q, q, mesh, mask=mask, causal=True)
            .astype(jnp.float32) ** 2)

    jitted = jax.jit(jax.value_and_grad(loss))
    cp_m, _ = _volumes(cv, jitted, (q,), "collective-permute")
    jit_u, args_u = cv.sp_ring()
    cp_u, _ = _volumes(cv, jit_u, args_u, "collective-permute")
    n = 8
    # folded mask is [B·H, T/n] f32 replicated per kv head row
    mask_bytes = h * (t // n) * 4
    extra = sum(cp_m) - sum(cp_u)
    # fwd + bwd each rotate the mask once per trip
    want_extra = 2 * n * mask_bytes
    assert 0 < extra <= want_extra * 1.3, (extra, want_extra)


def test_composed_dp_sp_tp_per_axis_gates(cv):
    """Composed DP×SP×TP step (VERDICT r4 Missing #1): every
    collective rides its OWN mesh axis — ppermutes only on 'seq'
    (inside the ring loop), gradient all-reduces only on 'data'/'seq'
    at gradient-byte volume, 'tensor' all-reduces only at activation
    scale (TP matmul partials), and no collective spans an unexpected
    axis combination."""
    step, args, ctx, axes = cv.composed_lm()
    with ctx:
        compiled = step.lower(*args).compile()
    colls = cv.collectives_with_axes(compiled, axes)
    assert colls, "composed step emitted no collectives"

    # 1. every collective's groups align to a mesh-axis subset
    unattributed = [(k, nb) for k, nb, ax, _ in colls if ax is None]
    assert not unattributed, unattributed

    # 2. ppermute: 'seq' only, inside the ring's while loop
    perms = [(ax, w) for k, nb, ax, w in colls
             if k == "collective-permute"]
    assert perms, "ring lost its collective-permutes"
    assert all(ax == ("seq",) and inwhile for ax, inwhile in perms), \
        perms

    # 3. gradient sync: hierarchical all-reduce over ('data',) and
    # ('seq',), each moving the per-device gradient bytes (TP-sharded
    # leaves count at 1/tensor_size)
    params = args[0]
    import numpy as np
    tp = axes["tensor"]
    grad_bytes = 0
    for leaf in jax.tree.leaves(params):
        nb = int(np.prod(leaf.shape)) * 4        # grads are f32
        sharded = any(ax == "tensor"
                      for ax in (leaf.sharding.spec or ()))
        grad_bytes += nb // tp if sharded else nb
    # band: the gate must catch the regression class (a lost gradient
    # sync drops the WHOLE volume; runaway gathering adds multiples),
    # not pin XLA's grouping choices — small tensors (loss mean, the
    # tied-embedding grad contribution) drift between allreduce groups
    # across compiles, so allow ±25% around the gradient bytes
    for axis in (("data",), ("seq",)):
        got = sum(nb for k, nb, ax, _ in colls
                  if k == "all-reduce" and ax == axis)
        assert grad_bytes * 0.75 < got < grad_bytes * 1.25, \
            (axis, got, grad_bytes)

    # 4. 'tensor' all-reduces are activation partials: each op at most
    # activation-cube bytes, never gradient-accumulated volume
    x = args[3]
    b, t = x.shape
    act_cap = b * t * 64 * 4          # [B, T, hidden*2] f32 headroom
    tensor_ars = [nb for k, nb, ax, _ in colls
                  if k == "all-reduce" and ax == ("tensor",)]
    assert tensor_ars, "TP lost its activation psums"
    assert max(tensor_ars) <= act_cap, (max(tensor_ars), act_cap)

    # 5. nothing reduces over an axis combo that would mean the
    # shardings collapsed (e.g. a single flat group of all 8)
    bad = [(k, ax) for k, nb, ax, _ in colls
           if k == "all-reduce" and ax is not None and len(ax) > 1]
    assert not bad, bad


def test_composed_without_tp_sharding_loses_tensor_psums(cv):
    """Canary: the same composed step with params fully REPLICATED
    (the lost-TP regression) emits no 'tensor'-axis activation
    all-reduce — proving gate #4 detects what it exists for."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel import (
        composed_context, composed_data_sharding, make_mesh)
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    model = CausalTransformerLM(
        vocab_size=64, hidden=32, n_layers=2, n_heads=2, max_len=32,
        ffn_mult=2.0, tie_embeddings=True, sequence_parallel="ring",
        seed=7)
    net = model.init(seq_len=32)
    mesh = make_mesh({"data": 2, "seq": 2, "tensor": 2})
    repl = NamedSharding(mesh, P())
    net.params = jax.tree.map(
        lambda x: jax.device_put(x, repl), net.params)
    net.opt_state = jax.tree.map(
        lambda x: jax.device_put(x, repl), net.opt_state)
    ds = composed_data_sharding(mesh)
    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32), ds)
    y = jax.device_put(
        jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32), ds)
    step = net._make_train_step()
    with composed_context(mesh):
        compiled = step.lower(
            net.params, net.opt_state, net.state, x, y, None, None,
            jax.random.PRNGKey(0)).compile()
    colls = cv.collectives_with_axes(
        compiled, dict(data=2, seq=2, tensor=2))
    tensor_ars = [nb for k, nb, ax, _ in colls
                  if k == "all-reduce" and ax == ("tensor",)]
    assert not tensor_ars, tensor_ars


def test_hierarchical_encoded_dp_dcn_volume(cv):
    """Two-tier DP (VERDICT r4 ask #6): dense f32 all-reduce stays on
    the intra-slice 'data' axis; only 2-bit-packed int32 words cross
    the 'slice' (DCN) axis — gathered bytes ≈ grad_bytes/16 per peer.
    The encoded path must never move dense f32 across 'slice'."""
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel import EncodedGradientsAccumulator
    from deeplearning4j_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"slice": 2, "data": 4})
    acc = EncodedGradientsAccumulator()
    g_shape = (64, 2048)                      # 512 KB f32 per device
    grads = {"w": jnp.ones((8,) + g_shape, jnp.float32) * 0.01}
    # state is PER-SLICE (leading slice axis, carried P("slice") —
    # see exchange_hierarchical's docstring)
    state = jax.tree.map(
        lambda x: jnp.stack([x, x]),
        acc.init_state({"w": grads["w"][0]}))

    def f(g, st):
        g = jax.tree.map(lambda x: x[0], g)   # per-device block
        st = jax.tree.map(lambda x: x[0], st)  # this slice's state
        out, st = acc.exchange_hierarchical(g, st, intra_axis="data",
                                            cross_axis="slice")
        expand = lambda x: jnp.asarray(x)[None]
        return (jax.tree.map(expand, out), jax.tree.map(expand, st))

    jitted = jax.jit(shard_map(
        f, mesh=mesh, in_specs=(P(("slice", "data")), P("slice")),
        out_specs=(P(("slice", "data")), P("slice")),
        check_vma=False))
    compiled = jitted.lower(grads, state).compile()
    colls = cv.collectives_with_axes(compiled,
                                     dict(slice=2, data=4))
    grad_bytes = int(np.prod(g_shape)) * 4

    # dense f32 reduction: 'data' only, grad-sized
    dense = [nb for k, nb, ax, _ in colls
             if k == "all-reduce" and ax == ("data",)]
    assert dense and grad_bytes * 0.95 < max(dense), (dense,
                                                      grad_bytes)
    # nothing grad-sized and dense crosses 'slice' (or spans both)
    for k, nb, ax, _ in colls:
        if ax is not None and "slice" in ax:
            assert nb <= grad_bytes / 8, (k, nb, ax)
    # the packed cross-slice gather exists and is ~1/16 wire: the
    # gathered result is [2, C] int32 where C = elements/16
    packed = [nb for k, nb, ax, _ in colls
              if k == "all-gather" and ax == ("slice",)]
    assert packed, "packed cross-slice exchange disappeared"
    want = 2 * grad_bytes / 16                # both slices' words
    assert want * 0.9 < max(packed) < want * 1.3, (packed, want)

"""Fused-primitive kernel library fences (ISSUE 15, ARCHITECTURE §17):

- interpret-mode fwd AND bwd parity for every fused norm kernel vs its
  XLA fallback (tight f32 band; documented bf16 band),
- byte-identity of the gate-off programs (the dispatch must be a pure
  trace-time decision: gate off == the pre-kernel expression, bitwise,
  with no custom calls in the lowered program),
- ``DL4J_TPU_KERNEL_FORCE`` exercises every gated dispatch site both
  ways on CPU CI (the testability satellite: without the flag the
  dispatch decision itself only ever runs on a TPU),
- warmup/aot_hits + zero-new-traces for the gather-overlap step pair,
- the gather-overlap trajectory fence (bit-identical to the
  end-gather sharded trajectory on the same mesh),
- the fused-diag-tap regression fence: the fused single-pass stat taps
  must cost well under half the legacy two-pass program's extra
  byte traffic (deterministic — XLA's own cost model, no wall clocks),
- the gap report's ``closed_by`` loop closure.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import fused_norms as fnorm
from deeplearning4j_tpu.ops import kernel_registry
from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture
def force_kernels(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")


def _rand(rng, *shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# interpret-mode parity (fwd + bwd) — the contract rule 9 anchors on
# ---------------------------------------------------------------------------

def test_rms_norm_parity(force_kernels, rng):
    x = _rand(rng, 24, 96)
    g = _rand(rng, 96)
    co = _rand(rng, 24, 96)
    out = fnorm.rms_norm(x, g)
    ref = fnorm.rms_norm_reference(x, g)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-6

    def loss(fn):
        return lambda x, g: jnp.sum(fn(x, g) * co)

    gk = jax.grad(loss(fnorm.rms_norm), argnums=(0, 1))(x, g)
    gr = jax.grad(loss(fnorm.rms_norm_reference), argnums=(0, 1))(x, g)
    for a, b in zip(gk, gr):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-6


def test_rms_norm_parity_3d_rows(force_kernels, rng):
    """[B, T, F] inputs fold to rows and unfold back — the layer-stack
    calling convention."""
    x = _rand(rng, 3, 17, 130)     # ragged rows + >128 features
    g = _rand(rng, 130)
    out = fnorm.rms_norm(x, g)
    ref = fnorm.rms_norm_reference(x, g)
    assert out.shape == x.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-6


def test_add_rms_norm_parity(force_kernels, rng):
    x = _rand(rng, 24, 96)
    d = _rand(rng, 24, 96)
    g = _rand(rng, 96)
    co = _rand(rng, 24, 96)
    y, s = fnorm.add_rms_norm(x, d, g)
    yr, sr = fnorm.add_rms_norm_reference(x, d, g)
    assert float(jnp.max(jnp.abs(y - yr))) < 2e-6
    assert float(jnp.max(jnp.abs(s - sr))) < 2e-6

    # both outputs carry cotangents (the residual stream continues)
    def loss(fn):
        def f(x, d, g):
            y, s = fn(x, d, g)
            return jnp.sum(y * co) + jnp.sum(s * s)
        return f

    gk = jax.grad(loss(fnorm.add_rms_norm), argnums=(0, 1, 2))(x, d, g)
    gr = jax.grad(loss(fnorm.add_rms_norm_reference),
                  argnums=(0, 1, 2))(x, d, g)
    for a, b in zip(gk, gr):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_layer_norm_parity(force_kernels, rng):
    x = _rand(rng, 24, 96)
    g = _rand(rng, 96)
    b = _rand(rng, 96)
    co = _rand(rng, 24, 96)
    out = fnorm.layer_norm(x, g, b)
    ref = fnorm.layer_norm_reference(x, g, b)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-6

    def loss(fn):
        return lambda x, g, b: jnp.sum(fn(x, g, b) * co)

    gk = jax.grad(loss(fnorm.layer_norm), argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(loss(fnorm.layer_norm_reference),
                  argnums=(0, 1, 2))(x, g, b)
    for a, b_ in zip(gk, gr):
        assert float(jnp.max(jnp.abs(a - b_))) < 1e-5


def test_parity_bf16_band(force_kernels, rng):
    """bf16 storage: the kernel upcasts to f32 internally (one rounding
    at write-out) while the fallback's jnp ops round per-op — agreement
    is to a bf16 band (a couple of ulps at the sampled |x| range;
    measured max 0.031 = 1 ulp at |x|~4), not bitwise."""
    x = _rand(rng, 16, 128).astype(jnp.bfloat16)
    g = _rand(rng, 128).astype(jnp.bfloat16)
    out = fnorm.rms_norm(x, g).astype(jnp.float32)
    ref = fnorm.rms_norm_reference(x, g).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(out - ref))) < 7e-2


def test_float64_always_falls_back(force_kernels, rng):
    """Semantic refusal: f64 (gradient checking) never dispatches."""
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float64)
    g = jnp.asarray(rng.standard_normal(32), jnp.float64)
    out = fnorm.rms_norm(x, g)
    ref = fnorm.rms_norm_reference(x, g)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# gate-off byte-identity: the dispatch is trace-time only
# ---------------------------------------------------------------------------

def _op_kinds(fn, *args):
    from deeplearning4j_tpu.obs import devtime
    text = jax.jit(fn).lower(*args).compile().as_text()
    sm = devtime.hlo_scope_map(text)
    kinds = {}
    for info in sm["ops"].values():
        kinds[info["kind"]] = kinds.get(info["kind"], 0) + 1
    return kinds, text


def test_gate_off_programs_unchanged(rng, monkeypatch):
    """With the gate off (CPU, no force flag) every dispatch site runs
    the EXACT pre-kernel expression: bitwise-equal outputs, identical
    op-kind histograms, and no custom calls in the compiled program."""
    monkeypatch.delenv("DL4J_TPU_KERNEL_FORCE", raising=False)
    x = _rand(rng, 8, 64)
    d = _rand(rng, 8, 64)
    g = _rand(rng, 64)
    b = _rand(rng, 64)
    cases = [
        (lambda: (lambda q: fnorm.rms_norm(q, g)),
         lambda: (lambda q: fnorm.rms_norm_reference(q, g))),
        (lambda: (lambda q: fnorm.layer_norm(q, g, b)),
         lambda: (lambda q: fnorm.layer_norm_reference(q, g, b))),
        (lambda: (lambda q: fnorm.add_rms_norm(q, d, g)),
         lambda: (lambda q: fnorm.add_rms_norm_reference(q, d, g))),
    ]
    for mk_gated, mk_ref in cases:
        gated, ref = mk_gated(), mk_ref()
        out_g = jax.jit(gated)(x)
        out_r = jax.jit(ref)(x)
        for a, bb in zip(jax.tree_util.tree_leaves(out_g),
                         jax.tree_util.tree_leaves(out_r)):
            assert np.array_equal(np.asarray(a), np.asarray(bb))
        kinds_g, text_g = _op_kinds(gated, x)
        kinds_r, _ = _op_kinds(ref, x)
        assert kinds_g == kinds_r
        assert "custom-call" not in text_g


# ---------------------------------------------------------------------------
# DL4J_TPU_KERNEL_FORCE: every gated dispatch site, both ways
# ---------------------------------------------------------------------------

def test_force_flag_routes_norm_layer_sites(rng, monkeypatch):
    """Each norm dispatch site (RMSNorm layer, LayerNormalization
    layer, TransformerDecoderBlock residual epilogue,
    nn.decoder_infer.rms)
    takes the kernel path under the force flag and the fallback
    without it — counted at the pallas-call wrappers, with outputs
    agreeing across the two dispatches."""
    from deeplearning4j_tpu.nn.layers.core import (LayerNormalization,
                                                   RMSNorm)
    from deeplearning4j_tpu.nn.decoder_infer import rms as gpt_rms

    calls = {"n": 0}
    orig_rms, orig_ln = fnorm._rms_fwd_call, fnorm._ln_fwd_call
    orig_add = fnorm._add_rms_fwd_call

    def wrap(fn):
        def inner(*a, **k):
            calls["n"] += 1
            return fn(*a, **k)
        return inner

    monkeypatch.setattr(fnorm, "_rms_fwd_call", wrap(orig_rms))
    monkeypatch.setattr(fnorm, "_ln_fwd_call", wrap(orig_ln))
    monkeypatch.setattr(fnorm, "_add_rms_fwd_call", wrap(orig_add))

    x = _rand(rng, 4, 48)
    rms = RMSNorm()
    p_rms, _, _ = rms.init(jax.random.PRNGKey(0), (48,))
    ln = LayerNormalization()
    p_ln, _, _ = ln.init(jax.random.PRNGKey(1), (48,))
    gam = _rand(rng, 48)
    delta = _rand(rng, 4, 48)

    def run_all():
        return (rms.apply(p_rms, {}, x)[0],
                ln.apply(p_ln, {}, x)[0],
                gpt_rms(x, gam),
                fnorm.add_rms_norm(x, delta, gam))

    monkeypatch.delenv("DL4J_TPU_KERNEL_FORCE", raising=False)
    off = run_all()
    assert calls["n"] == 0            # gate off: no kernel dispatch
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    on = run_all()
    assert calls["n"] >= 4            # every site took the kernel path
    for a, b in zip(jax.tree_util.tree_leaves(off),
                    jax.tree_util.tree_leaves(on)):
        assert float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                     - jnp.asarray(b, jnp.float32)))) \
            < 1e-5


def test_force_flag_routes_flash_site(rng, monkeypatch):
    """``scaled_dot_attention``'s flash gate: forced, a shape far
    below DL4J_TPU_FLASH_MIN_T dispatches the interpret-mode kernel
    (counted); unforced on CPU it stays on the einsum. Semantic
    refusals hold under force."""
    from deeplearning4j_tpu.nn.layers import attention as att

    q = _rand(rng, 1, 64, 2, 16)
    k = _rand(rng, 1, 64, 2, 16)
    v = _rand(rng, 1, 64, 2, 16)
    calls = {"n": 0}
    orig = pk.flash_attention

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(pk, "flash_attention", counting)
    monkeypatch.delenv("DL4J_TPU_KERNEL_FORCE", raising=False)
    ref = att.scaled_dot_attention(q, k, v, causal=True)
    assert calls["n"] == 0
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    out = att.scaled_dot_attention(q, k, v, causal=True)
    assert calls["n"] == 1
    assert float(jnp.max(jnp.abs(out - ref))) < 5e-5
    # semantic refusal survives the force: causal Tq > Tk stays einsum
    q_long = _rand(rng, 1, 96, 2, 16)
    att.scaled_dot_attention(q_long, k, v, causal=True)
    assert calls["n"] == 1


# ---------------------------------------------------------------------------
# gather-overlap: trajectory fence + warmup/zero-retrace fence
# ---------------------------------------------------------------------------

def _mlp_net(seed=7):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Adam(learning_rate=1e-3)).list()
            .layer(DenseLayer(n_out=32, activation="relu"))
            .layer(OutputLayer(n_out=4, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def _toy_it(batch=64):
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, 16)).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, batch)]
    return ListDataSetIterator(DataSet(x, y), batch_size=batch)


needs_mesh = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


@needs_mesh
def test_gather_overlap_trajectory_matches_sharded():
    """The overlap step is the sharded step with the gather moved
    across the step boundary — same math, so the trajectory is
    BIT-identical to end-gather sharded on the same mesh (unlike the
    vs-replicated comparison, the two programs share the scatter/
    update/gather building blocks)."""
    from deeplearning4j_tpu.parallel import ParallelWrapper

    def drive(**kw):
        net = _mlp_net()
        w = ParallelWrapper(net, workers=8, sharded_update=True, **kw)
        w.fit(_toy_it(), epochs=8)
        return net.params

    p_sh = drive()
    p_ov = drive(gather_overlap=True)
    for a, b in zip(jax.tree_util.tree_leaves(p_sh),
                    jax.tree_util.tree_leaves(p_ov)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@needs_mesh
def test_gather_overlap_respects_params_reassignment():
    """Assigning ``net.params`` between fits (loaded weights,
    transfer learning) must feed the NEXT overlap fit — the carried
    shards re-derive from the authoritative params at fit entry
    (review fix: they previously kept training the pre-assignment
    weights)."""
    from deeplearning4j_tpu.parallel import ParallelWrapper

    def drive(reassign):
        net = _mlp_net()
        w = ParallelWrapper(net, workers=8, sharded_update=True,
                            gather_overlap=True)
        w.fit(_toy_it(), epochs=2)
        if reassign is not None:
            net.params = jax.tree_util.tree_map(
                lambda l: jnp.zeros_like(l), net.params)
        w.fit(_toy_it(), epochs=1)
        return net.params

    p_cont = drive(None)
    p_zero = drive("zeros")
    # one step from zeros lands near zero (lr=1e-3); continuing the
    # old trajectory would keep O(initializer)-scale weights
    w_cont = np.abs(np.asarray(
        jax.tree_util.tree_leaves(p_cont)[0])).max()
    w_zero = np.abs(np.asarray(
        jax.tree_util.tree_leaves(p_zero)[0])).max()
    assert w_zero < 0.05 < w_cont, (w_zero, w_cont)


@needs_mesh
def test_gather_overlap_warmup_zero_retraces():
    """Warmup AOT-compiles the overlap step AND its diag sibling; the
    first real batches dispatch to the warmed executables (aot_hits)
    with zero new traces under the strict sentry."""
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.perf.warmup import WarmupSpec

    net = _mlp_net(seed=11)
    net.monitor_numerics(every=2)
    w = ParallelWrapper(net, workers=8, sharded_update=True,
                        gather_overlap=True)
    rep = w.warmup([WarmupSpec(features=(64, 16), labels=(64, 4))])
    assert rep["compiled"] == 2          # step + diag sibling
    before = sentry.total_traces()
    with sentry.strict(budget=0):
        w.fit(_toy_it(), epochs=2)
    assert sentry.total_traces() == before
    st = sentry.stats()
    assert st["ParallelWrapper.sync_sharded_overlap_step"][
        "aot_hits"] >= 1
    assert st["ParallelWrapper.sync_sharded_overlap_diag_step"][
        "aot_hits"] >= 1


# ---------------------------------------------------------------------------
# fused diag taps: deterministic cost fence (no wall clocks)
# ---------------------------------------------------------------------------

def test_fused_diag_taps_beat_twopass_baseline():
    """The fused-tap diagnostic program must move LESS THAN HALF the
    extra bytes the legacy two-pass program moved over the plain step
    (measured 6x less on the smoke LeNet — the ~17% → ≤8% diag-cost
    acceptance). XLA's own ``cost_analysis`` makes the fence
    deterministic: no wall clocks, no shared-CI-box noise."""
    from deeplearning4j_tpu.obs import numerics
    from deeplearning4j_tpu.zoo import LeNet

    b = 64
    key = jax.random.fold_in(jax.random.PRNGKey(0), 0)
    x = jax.ShapeDtypeStruct((b, 28, 28, 1), jnp.float32)
    y = jax.ShapeDtypeStruct((b, 10), jnp.float32)

    def program_bytes(step, net):
        step.warmup(net.params, net.opt_state, net.state, x, y,
                    None, None, key)
        ex = list(step._aot.values())[0]
        ca = ex.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        return float(ca.get("bytes accessed", 0.0))

    net = LeNet(num_classes=10, seed=0).init()
    net.monitor_numerics(every=1, raise_on_nonfinite=False)
    plain = program_bytes(net._make_train_step(), net)
    fused = program_bytes(net._make_diag_step(), net)
    orig = numerics.act_summary
    try:
        numerics.act_summary = numerics.act_summary_twopass
        legacy = program_bytes(net._make_diag_step(), net)
    finally:
        numerics.act_summary = orig
    assert fused > plain                  # the taps are real
    assert legacy > plain
    assert (fused - plain) < 0.5 * (legacy - plain), (
        f"fused diag taps move {fused - plain:.3e} extra bytes vs "
        f"legacy {legacy - plain:.3e} — the fused-tap win regressed")


def test_fused_moments_matches_masked_stats(rng):
    """fused_moments == the straightforward masked reductions,
    including non-finite entries."""
    from deeplearning4j_tpu.obs import numerics

    v = rng.standard_normal((100,)).astype(np.float32)
    v[7] = np.nan
    v[13] = np.inf
    x = jnp.asarray(v)
    s1, s2, mx, n_ok = jax.jit(numerics.fused_moments)(x)
    finite = np.isfinite(v)
    safe = np.where(finite, v, 0.0)
    assert float(s1) == pytest.approx(float(safe.sum()), rel=1e-6)
    assert float(s2) == pytest.approx(float((safe ** 2).sum()),
                                      rel=1e-6)
    assert float(mx) == pytest.approx(float(np.abs(safe).max()))
    assert int(n_ok) == int(finite.sum())


# ---------------------------------------------------------------------------
# gap-report loop closure
# ---------------------------------------------------------------------------

def test_gap_report_marks_closed_scopes(monkeypatch):
    """A norm scope that dispatches to a registered kernel (gate
    active) reports closed_by and stops being a candidate; with the
    gate off the gap stays open."""
    from deeplearning4j_tpu.obs import devtime

    cap = {"scopes": {
        "layer_3.RMSNorm": {
            "device_ms": 8.0, "share": 0.4, "ops": 10, "fusions": 2,
            "backward_ms": 4.0, "custom_call_ms": 0.0, "flops": 1e9,
            "bytes": 1e8, "kinds": {"multiply": 4},
            "roofline": {"utilization": 0.1, "bound": "memory"}},
        "layer_0.DenseLayer": {
            "device_ms": 6.0, "share": 0.3, "ops": 10, "fusions": 2,
            "backward_ms": 3.0, "custom_call_ms": 0.0, "flops": 1e9,
            "bytes": 1e8, "kinds": {"dot": 4},
            "roofline": {"utilization": 0.1, "bound": "memory"}},
    }}
    monkeypatch.delenv("DL4J_TPU_KERNEL_FORCE", raising=False)
    gaps = {g["scope"]: g for g in devtime.gap_report(cap)}
    # CPU, no force: the rms kernel's gate is off -> gap stays open
    assert gaps["layer_3.RMSNorm"]["closed_by"] is None
    assert gaps["layer_3.RMSNorm"]["pallas_candidate"] is True
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    gaps = {g["scope"]: g for g in devtime.gap_report(cap)}
    assert gaps["layer_3.RMSNorm"]["closed_by"] == "rms_norm"
    assert gaps["layer_3.RMSNorm"]["pallas_candidate"] is False
    # no kernel covers a Dense matmul scope — still a candidate
    assert gaps["layer_0.DenseLayer"]["closed_by"] is None
    assert gaps["layer_0.DenseLayer"]["pallas_candidate"] is True


def test_registry_entries_resolve():
    """Every registry entry's fallback exists and is callable, and the
    closed gauge semantics follow gate_active."""
    from deeplearning4j_tpu.ops import fused_norms, moe, pallas_kernels
    mods = {"ops/pallas_kernels.py": pallas_kernels,
            "ops/fused_norms.py": fused_norms, "ops/moe.py": moe}
    for name, entry in kernel_registry.KERNEL_REGISTRY.items():
        mod = mods[entry["module"]]
        assert callable(getattr(mod, entry["fallback"])), name
        assert entry["scope"].startswith("ops."), name

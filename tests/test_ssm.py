"""The Mamba-2 mixer (``ops/ssm.py``) and the HYBRID decoder
(``CausalTransformerLM(mixer="hybrid")``): the chunked form against the
one-position recurrence, the convolution's carried tail, the decode
kernel against its fallback, the 64-wide paged attention, the model's
three forwards, and the gateway's chunk admission then decode through
BOTH pools of the one pager, held against the benchmark's plain
reference (logits, not tokens).
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import ssm
from deeplearning4j_tpu.serving import DecodeScheduler
from deeplearning4j_tpu.serving import kv_pager
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

KINDS = ("mamba2", "mamba2", "softmax", "mamba2", "softmax", "mamba2")
SPEC = ssm.HybridSpec(
    kinds=KINDS, d_inner=128, n_heads=8, d_state=16, d_conv=4, chunk=16,
    norm_eps=1e-5)
#: the published multipliers, the model's own arguments
SCALARS = dict(embedding_multiplier=12.0, residual_multiplier=0.22,
               logits_scaling=8.0, attention_multiplier=0.5,
               norm_eps=1e-5)
#: the benchmark's names for the same sizes (the reference reads these)
_TOY = dict(
    layer_types=["mamba" if k == "mamba2" else "attention" for k in KINDS],
    num_local_experts=0, mamba_n_groups=1,
    position_embedding_type="nope", tie_word_embeddings=True,
    attention_bias=False, mamba_proj_bias=False, rms_norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, attention_multiplier=0.5, num_attention_heads=4,
    num_key_value_heads=2, mamba_n_heads=8, mamba_d_state=16)


def _model(**kw):
    return CausalTransformerLM(
        vocab_size=64, hidden=64, n_layers=len(KINDS), n_heads=4,
        n_kv_heads=2, max_len=kw.pop("max_len", 128), ffn_mult=2,
        rope_theta=None, tie_embeddings=True, mixer="hybrid",
        hybrid=kw.pop("hybrid", SPEC),
        updater=kw.pop("updater", upd.Sgd(0.0)), seed=3,
        **{**SCALARS, **kw})


def _seeded(net, seed=0):
    """The benchmark's draw at toy size: embedding rows at unit norm
    after the multiplier, so that the tied head does not predict every
    position's own input."""
    from benchmarks.models import hybrid_ssm_lm as builder
    cfg = dict(_TOY, embedding_multiplier=12.0, hidden_size=64,
               mamba_d_conv=4, mamba_conv_bias=True)
    net.params = builder.make_weights(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                     net.params), seed, builder.init_of(cfg))
    return net


@pytest.fixture(scope="module")
def hybrid_lm():
    model = _model()
    return model, _seeded(model.init())


# -- ops/ssm.py ----------------------------------------------------------

def _mha(seed, f=32, spec=SPEC):
    rng = np.random.default_rng(seed)
    n = spec.n_heads
    step = np.exp(rng.uniform(np.log(1e-2), np.log(0.3), n))
    return {
        "Win": jnp.asarray(rng.standard_normal((f, spec.in_width))
                           / np.sqrt(f), jnp.float32),
        "conv_w": jnp.asarray(rng.uniform(-.5, .5, (4, spec.conv_dim)),
                              jnp.float32),
        "conv_b": jnp.asarray(rng.uniform(-.5, .5, (spec.conv_dim,)),
                              jnp.float32),
        "dt_bias": jnp.asarray(step + np.log(-np.expm1(-step)),
                               jnp.float32),
        "A_log": jnp.asarray(np.log(rng.uniform(1, 16, n)), jnp.float32),
        "D": jnp.ones((n,), jnp.float32),
        "norm_gamma": jnp.asarray(rng.uniform(.5, 1.5, spec.d_inner),
                                  jnp.float32),
        "Wo": jnp.asarray(rng.standard_normal((spec.d_inner, f))
                          / np.sqrt(spec.d_inner), jnp.float32)}


def _by_steps(mha, h, valid):
    """Rows ``h [B, T, F]`` one position at a time, a row that is not
    valid skipped: what the chunked form has to equal."""
    b, t, _ = h.shape
    state, tail = ssm.zero_state(b, SPEC, jnp.float32)
    out = np.zeros((b, t, SPEC.d_inner), np.float32)
    for i in range(t):
        a, s1, t1 = ssm.mixer_rows(mha, h[:, i], SPEC, state, tail)
        keep = np.asarray(valid[:, i])
        state = jnp.where(keep[:, None, None], s1, state)
        tail = jnp.where(keep[:, None, None], t1, tail)
        out[:, i] = np.where(keep[:, None], np.asarray(a), 0.0)
    return out, state, tail


def test_chunked_form_equals_the_recurrence_position_by_position():
    """Three chunks of 16, the middle one with padding rows in its
    tail (10 valid), the last one whole: the chunked form carrying
    state and tail from chunk to chunk against the one-position
    recurrence over the valid rows alone."""
    mha = _mha(1)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((2, 48, 32)), jnp.float32)
    valid = np.ones((2, 48), bool)
    valid[:, 26:32] = False
    want, state_w, tail_w = _by_steps(mha, h, valid)
    state, tail = ssm.zero_state(2, SPEC, jnp.float32)
    got = []
    for c in range(3):
        rows = slice(16 * c, 16 * c + 16)
        a, state, tail = ssm.mixer_chunk(
            mha, h[:, rows], SPEC, jnp.asarray(valid[:, rows]), state,
            tail)
        got.append(np.where(valid[:, rows, None], np.asarray(a), 0.0))
    np.testing.assert_allclose(np.concatenate(got, 1), want, atol=2e-5)
    np.testing.assert_allclose(state, state_w, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(tail_w))


def test_a_long_prompt_scans_its_chunks():
    """``mixer_chunk`` over more rows than a chunk (dense prefill, the
    training forward) is the chunks walked in order."""
    mha = _mha(3)
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.standard_normal((1, 41, 32)), jnp.float32)
    valid = jnp.asarray(np.arange(41)[None] < 37)
    want, state_w, tail_w = _by_steps(mha, h, np.asarray(valid))
    a, state, tail = ssm.mixer_chunk(
        mha, h, SPEC, valid, *ssm.zero_state(1, SPEC, jnp.float32))
    np.testing.assert_allclose(np.asarray(a)[:, :37], want[:, :37],
                               atol=2e-5)
    np.testing.assert_allclose(state, state_w, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(tail_w))


def test_convolution_is_causal_with_zeros_before_the_sequence():
    mha = _mha(5)
    rng = np.random.default_rng(6)
    xbc = rng.standard_normal((1, 9, SPEC.conv_dim)).astype(np.float32)
    y, tail = ssm.conv_chunk(
        mha, jnp.asarray(xbc), ssm.zero_state(1, SPEC, jnp.float32)[1],
        jnp.ones((1, 9), bool))
    w, b = np.asarray(mha["conv_w"]), np.asarray(mha["conv_b"])
    padded = np.concatenate([np.zeros((3, SPEC.conv_dim)), xbc[0]])
    want = b + sum(w[j] * padded[j:j + 9] for j in range(4))
    np.testing.assert_allclose(y[0], want / (1 + np.exp(-want)),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tail[0]), xbc[0, -3:])


@pytest.mark.parametrize("where", ["left", "inside"])
def test_the_training_mixer_skips_masked_rows_wherever_they_lie(where):
    """A left-padded batch, and one with masked rows inside it: a
    sequence is its valid rows, to the convolution as to the state
    (``conv_chunk`` alone takes padding only after the tokens)."""
    from deeplearning4j_tpu.nn.layers.attention import Mamba2Mixer
    mha = _mha(7)
    rng = np.random.default_rng(8)
    rows = jnp.asarray(rng.standard_normal((2, 20, 32)), jnp.float32)
    junk = jnp.asarray(rng.standard_normal((2, 27, 32)), jnp.float32)
    at = (np.arange(7, 27) if where == "left"
          else np.delete(np.arange(27), [3, 4, 11, 12, 13, 19, 25]))
    mask = np.zeros((2, 27), np.float32)
    mask[:, at] = 1.0
    layer = Mamba2Mixer(n_in=32, spec=SPEC)
    want, _ = layer.apply(mha, {}, rows)
    got, _ = layer.apply(mha, {}, junk.at[:, at].set(rows),
                         mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got)[:, at], want, atol=2e-5)
    assert not np.asarray(got)[:, np.setdiff1d(np.arange(27), at)].any()


def test_hybrid_spec_is_checked_and_indexes_each_kind():
    assert SPEC.layers("softmax") == (2, 4)
    assert [SPEC.index(i) for i in range(6)] == [0, 1, 0, 2, 1, 3]
    assert SPEC.conv_dim == 128 + 32 and SPEC.in_width == 256 + 32 + 8
    assert di.q_fold(_model(), 64) == 4.0
    assert di.q_fold(_model(attention_multiplier=None), 64) is None
    assert ssm.HybridSpec.of(SPEC.to_dict()) == SPEC
    with pytest.raises(ValueError, match="layer kinds"):
        ssm.HybridSpec(kinds=("mamba",), d_inner=8, n_heads=2, d_state=4)
    with pytest.raises(ValueError, match="divisible"):
        ssm.HybridSpec(kinds=("mamba2",), d_inner=9, n_heads=2,
                       d_state=4)


# -- the kernels ---------------------------------------------------------

def _decode_case(rng, n_s, active, layers=2, n=128, heads=4, p=64):
    hp = heads * p
    pool = jnp.asarray(rng.standard_normal((layers, n_s + 1, n, hp)),
                       jnp.float32)
    x = jnp.asarray(rng.standard_normal((n_s, hp)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n_s, n)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((n_s, n)), jnp.float32)
    delta = jnp.asarray(rng.uniform(0.01, 0.3, (n_s, heads)), jnp.float32)
    a_neg = -jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)
    d_skip = jnp.asarray(rng.uniform(.5, 1.5, heads), jnp.float32)
    return (x, b, c, delta, a_neg, d_skip, pool, 1,
            jnp.arange(1, n_s + 1), jnp.asarray(active))


#: (slots a phase, copies a slot) at the test's shapes (5 slots of
#: [128, 256]): what the two constants give, and every other value
#: they may: one or two 128-lane parts, groups that divide the live
#: slots and groups that do not
_SSM_FORMS = {"as-shaped": None, "g1-p2": (1, 2), "g2-p2": (2, 2),
              "g2-p1": (2, 1), "g3-p1": (3, 1)}


@pytest.mark.parametrize("form", list(_SSM_FORMS))
@pytest.mark.parametrize("active", [
    (True,) * 5, (False, True, True, False, True),
    (False, False, True, False, False), (False,) * 5,
    (False, False, False, False, True), (True, False, True, False, True),
    (False, True, True, False, False)],
    ids=["all", "first-idle", "one", "none", "last", "apart",
         "neighbours"])
def test_ssm_decode_matches_reference(monkeypatch, rng, active, form):
    """The kernel (interpret mode) against the registered fallback,
    layer 1 of 2, in every form its two constants can give it at these
    shapes: outputs, the live slots' pages (updated where they lie),
    and every page the call must not touch (the other layer, an
    inactive slot's, the trash page) bit for bit."""
    args = _decode_case(rng, 5, active)
    pool, live = args[6], np.asarray(active)
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "0")
    y_ref, p_ref = pk.ssm_decode(*args)
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    assert pk._use_ssm_kernel(args[1], args[0])
    if _SSM_FORMS[form] is None:
        assert pk._ssm_form(5, 128, 256) == (5, 1)
    else:
        group, parts = _SSM_FORMS[form]
        monkeypatch.setattr(pk, "_SSM_PHASE_BYTES", group * 4 * 128 * 256)
        monkeypatch.setattr(pk, "_SSM_PART_COLS", 256 // parts)
        assert pk._ssm_form(5, 128, 256) == (group, parts)
    y, new = pk.ssm_decode(*args)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    assert (np.asarray(y)[~live] == 0).all()
    pages = 1 + np.arange(5)
    np.testing.assert_allclose(new[1, pages[live]], p_ref[1, pages[live]],
                               atol=1e-6)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, pages[~live]],
                                  pool[1, pages[~live]])
    np.testing.assert_array_equal(new[1, 0], pool[1, 0])    # the trash
    # the recurrence itself, in the stored layout
    decay = np.repeat(np.exp(np.asarray(args[3] * args[4])), 64, -1)
    dx = np.repeat(np.asarray(args[3]), 64, -1) * np.asarray(args[0])
    want = (decay[:, None] * np.asarray(pool[1, pages])
            + np.asarray(args[1])[:, :, None] * dx[:, None])
    np.testing.assert_allclose(new[1, pages[live]], want[live], atol=1e-5)


def test_shapes_the_ssm_kernel_does_not_take_run_the_fallback(
        monkeypatch, rng):
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    args = _decode_case(rng, 3, (True, False, True), n=16, heads=8, p=16)
    assert not pk._use_ssm_kernel(args[1], args[0])
    y, new = pk.ssm_decode(*args)
    assert np.isfinite(np.asarray(y)).all() and (np.asarray(y)[1] == 0).all()


def test_a_traced_program_says_the_state_kernel_s_form(monkeypatch, rng):
    """The kernel is chosen once a program, at trace time: the
    ``compile/jaxpr_trace`` record of the sentried program carries how
    many kernel calls it holds and their form (a state's bytes, the
    copies a state, the states of buffers in VMEM); a program that
    keeps the fallback says nothing."""
    from deeplearning4j_tpu.obs import trace
    from deeplearning4j_tpu.perf import sentry
    args = _decode_case(rng, 5, (True, False, True, True, True))

    def two_layers(*a):
        y, pool = pk.ssm_decode(*a)
        return pk.ssm_decode(*a[:6], pool, 0, *a[8:])

    def said(name):
        t0 = trace.now()
        sentry.jit(two_layers, name=name)(*args)
        return [r.counts for r in trace.records(t0)
                if r.name == "compile/jaxpr_trace" and r.cause == name
                and r.counts and "ssm_decode_kernels" in r.counts]

    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "0")
    assert said("test.two_ssm_layers.fallback") == []
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    notes = said("test.two_ssm_layers")
    assert len(notes) == 1, notes
    assert {k: notes[0][k] for k in (
        "ssm_decode_kernels", "state_bytes", "state_parts",
        "state_buffers")} == {
            "ssm_decode_kernels": 2, "state_bytes": 4 * 128 * 256,
            "state_parts": 1, "state_buffers": 10}


_PACKED_WALKS = ("first-inactive", "inactive-between", "none-live",
                 "chunk-plus-one-row", "page-counts-of-all-bits",
                 "short-after-long")


_PACKED_CASES = [
    pytest.param(dtype, tol, chunk, None, id=f"{chunk}-{dt}")
    for dtype, tol, dt in ((jnp.float32, 2e-5, "float32"),
                           (jnp.bfloat16, 3e-2, "bfloat16"))
    for chunk in (2, None)
] + [pytest.param(jnp.float32, 2e-5, chunk, walk, id=f"{chunk}-{walk}")
     for walk in _PACKED_WALKS for chunk in (2, None)]


@pytest.mark.parametrize("dtype,tol,pages_per_chunk,walk", _PACKED_CASES)
def test_paged_decode_takes_heads_of_64(monkeypatch, rng, dtype, tol,
                                        pages_per_chunk, walk):
    """K and V as the two halves of ONE 128-lane row: the packed form
    of the kernel (interpret mode) against the registered fallback,
    over the ragged batch and over the walks that try the pipeline's
    edges (``test_pallas._PAGED_WALKS``)."""
    from test_pallas import _PAGED_N_LIVE, _PAGED_WALKS, _paged_case
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    lengths, mp = ((_PAGED_N_LIVE, 6) if walk is None
                   else (_PAGED_WALKS[walk], 8))
    q, pool, pt, n_live = _paged_case(rng, dtype, 32, 8, 64, 16, mp,
                                      lengths)
    assert pk._use_paged_kernel(q, (pool,))
    out = np.asarray(pk.paged_decode_attention(
        q, (pool,), 1, pt, n_live, pages_per_chunk=pages_per_chunk),
        np.float32)
    assert out.shape == (len(lengths), 32, 64)
    ref = np.asarray(pk._reference_paged_attention(
        q[:, None], (jnp.nan_to_num(pool),), 1, pt,
        (n_live - 1)[:, None])[:, 0], np.float32)
    live = np.asarray(n_live) > 0
    assert not np.isnan(out).any()          # trash was never read
    assert np.abs(out[live] - ref[live]).max(initial=0.0) < tol
    assert (out[~live] == 0).all()
    # heads of 32 fill neither a tile nor half of one
    assert not pk._use_paged_kernel(q[..., :32], (pool,))


# -- the model -----------------------------------------------------------

def test_mixer_arguments_are_checked():
    with pytest.raises(ValueError, match="come together"):
        CausalTransformerLM(vocab_size=8, hidden=8, n_layers=1, n_heads=1,
                            mixer="hybrid")
    with pytest.raises(ValueError, match="come together"):
        CausalTransformerLM(vocab_size=8, hidden=8, n_layers=1, n_heads=1,
                            hybrid=SPEC)
    with pytest.raises(ValueError, match="names 6 layers"):
        _model_of(n_layers=5)
    for kw in (dict(cache_quant="int8"), dict(serve_quant="int8"),
               dict(sequence_parallel="ring")):
        with pytest.raises(ValueError, match="do not apply"):
            _model(**kw)


def _model_of(n_layers):
    return CausalTransformerLM(vocab_size=8, hidden=64, n_layers=n_layers,
                               n_heads=4, mixer="hybrid", hybrid=SPEC)


def test_the_blocks_serialize_with_their_kinds(hybrid_lm):
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
    model, _ = hybrid_lm
    conf = model.conf(32)
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert [l.mixer for l in back.layers[1:7]] == list(KINDS)
    assert ssm.HybridSpec.of(back.layers[1].hybrid) == SPEC
    assert back.layers[0].multiplier == 12.0
    assert back.layers[7].multiplier == 1 / 8
    assert back.layers[3].rope_theta is None
    assert (back.layers[3].residual_multiplier, back.layers[3].score_scale,
            back.layers[3].norm_eps) == (0.22, 0.5, 1e-5)


def test_generate_equals_the_training_forward(hybrid_lm):
    model, net = hybrid_lm
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, (2, 19)).astype(np.int32)
    out = model.generate(net, prompt, 9)
    probs = np.asarray(net.output(out[:, :-1]))
    np.testing.assert_array_equal(probs.argmax(-1)[:, 18:], out[:, 19:])
    assert len({int(t) for t in out[0, 19:]}) > 2     # not one token


def test_fit_trains_the_hybrid_model():
    model = _model(updater=upd.Adam(learning_rate=2e-2), max_len=32)
    net = _seeded(model.init(32))
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 64, (8, 33)).astype(np.int32)
    x, y = toks[:, :-1], toks[:, 1:]
    scores = []
    for _ in range(16):
        net.fit(x, y)
        scores.append(float(net.score()))
    assert np.isfinite(scores).all() and scores[-1] < 0.9 * scores[0]


# -- the gateway's path against the plain reference ----------------------

class _Req:
    def __init__(self, prompt, max_new, stop_at=None):
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new, self.temperature = max_new, None
        self.stop_at = stop_at
        self.tokens, self.done = [], False

    @property
    def eos_id(self):       # ends at its stop_at-th token, whatever
        return (self.tokens[-1] if len(self.tokens) == self.stop_at
                else None)

    def push(self, tok):
        self.tokens.append(int(tok))

    def finish(self):
        self.done = True

    def fail(self, e):
        raise e


def _served_logits(model, net, seq, t0, round_state=None):
    """Teacher-forced logits of ``seq[t0 - 1:]`` by the gateway's own
    programs: chunk admission over both pools, then THE paged block a
    position at a time. With ``round_state`` the state pool is rounded
    to that dtype after every program."""
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=96)
    assert sched.prefill_chunk == 16

    def kept(pool):
        if round_state is None:
            return pool
        return (pool[0], pool[1].astype(round_state).astype(jnp.float32),
                pool[2])

    other = _Req(np.arange(5) % 64, 40)     # ours is not in slot 0
    assert sched.admit(other)
    req = _Req(seq[:t0], len(seq) - t0 + 1)
    assert sched.admit(req)
    sched.pager.pool = kept(sched.pager.pool)
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
        sched.pager.pool = kept(pool)
        rows.append(np.asarray(logits[slot], np.float32))
    return req.tokens[0], np.stack(rows)


def _reference_logits(params, seq, t0, rows):
    from benchmarks.reference import hybrid_ssm_lm as ref
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_from(
            params, jnp.asarray(seq), t0 - 1, rows=rows, **ref.dims(_TOY)))


#: float32 on the CPU, logits of 0.05 in size: the chunked admission
#: and the paged recurrence differ from the reference's one scan in
#: the order of float32 sums (read: 3e-8 at most); a state held in
#: bf16 moves a logit by 2e-5 or more, every other fault by more
LOGIT_TOL = 5e-7


def _seq(t0, n=12, seed=7):
    return np.random.default_rng(seed + t0).integers(
        0, 64, t0 + n).astype(np.int32)


@pytest.mark.parametrize("t0", [16, 23, 41])
def test_chunk_admission_then_paged_decode_matches_the_reference_logits(
        hybrid_lm, t0):
    """A prompt of one, two and three chunks (the last two with
    padding), then twelve decode steps through the state pool and the
    KV pages: every logit against the reference's one full forward."""
    model, net = hybrid_lm
    seq = _seq(t0)
    first, got = _served_logits(model, net, seq, t0)
    want = _reference_logits(net.params, seq, t0, len(seq) - t0 + 1)
    assert np.abs(want).max() > 0.02
    assert first == int(want[0].argmax())
    np.testing.assert_allclose(got, want[1:], atol=LOGIT_TOL)


def test_generate_s_dense_caches_match_the_reference_logits(hybrid_lm):
    model, net = hybrid_lm
    seq = _seq(29)

    @jax.jit
    def dense(params, toks):
        logits0, caches = model._prefill_forward(
            params, toks[None, :32], 32 + 12, jnp.asarray(29))
        rows = [logits0[0]]
        for j in range(12):
            logits, caches = model._token_logits(
                params, toks[None, 29 + j], caches, 29 + j)
            rows.append(logits[0])
        return jnp.stack(rows)

    toks = np.zeros(48, np.int32)
    toks[:41] = seq
    got = np.asarray(dense(model.decode_params(net), jnp.asarray(toks)))
    want = _reference_logits(net.params, seq, 29, 13)
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


def _bf16_state(mp):
    return dict(round_state=jnp.bfloat16)


def _tail_not_carried(mp):
    state = kv_pager.SSMChunk.state
    mp.setattr(kv_pager.SSMChunk, "state", lambda self, li: (
        state(self, li)[0], jnp.zeros_like(state(self, li)[1])))


def _state_not_emptied(mp):
    # a page handed on must start its next sequence from nothing
    def state(self, li):
        h, tails = self.pool
        return (h[li, self.page][None],
                tails[li, self.page].reshape(1, 3, -1))
    mp.setattr(kv_pager.SSMChunk, "state", state)


def _no_dt_bias(mp):
    step_size = ssm.step_size
    mp.setattr(ssm, "step_size", lambda mha, dt: step_size(
        dict(mha, dt_bias=jnp.zeros_like(mha["dt_bias"])), dt))


def _no_skip(mp):
    rows, chunk = ssm.mixer_rows, ssm.mixer_chunk

    def without(fn):
        return lambda mha, *a, **kw: fn(
            dict(mha, D=jnp.zeros_like(mha["D"])), *a, **kw)

    mp.setattr(ssm, "mixer_rows", without(rows))
    mp.setattr(ssm, "mixer_chunk", without(chunk))


def _scores_by_root_d(mp):
    mp.setattr(di, "q_fold", lambda dims, d: 1.0)


def _rotary(mp):
    rotary_rows = di.rotary_rows
    mp.setattr(di, "rotary_rows",
               lambda x, theta, pos: rotary_rows(x, 10000.0, pos))


@pytest.mark.parametrize("fault", [
    _bf16_state, _tail_not_carried, _no_dt_bias, _no_skip,
    _scores_by_root_d, _rotary], ids=lambda f: f.__name__[1:])
def test_a_fault_moves_the_served_logits(hybrid_lm, monkeypatch, fault):
    """What the cell's comparison reads through served tokens, read
    here at the logits, where a state held in bf16 shows too (it moves
    a logit by 1e-3 of its size: under the spacing of the two best at
    toy size, so the cell's token comparison cannot see it)."""
    model, net = hybrid_lm
    seq = _seq(41)
    kw = fault(monkeypatch) or {}
    _, got = _served_logits(model, net, seq, 41, **kw)
    want = _reference_logits(net.params, seq, 41, 13)[1:]
    assert np.abs(got - want).max() > 20 * LOGIT_TOL


def test_bf16_serving_stays_within_bf16_of_the_reference():
    model = _model(compute_dtype="bfloat16")
    net = _seeded(model.init())
    net.params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), net.params)
    assert model.decode_params(net) is net.params
    seq = _seq(41)
    _, got = _served_logits(model, net, seq, 41)
    want = _reference_logits(net.params, seq, 41, 13)[1:]
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()
    sched = DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=64)
    kv, state, tail = sched.pager.pool
    assert (kv.dtype, state.dtype, tail.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.bfloat16)


# -- one pager, two kinds of state ---------------------------------------

def _own_records(mark):
    import threading

    from deeplearning4j_tpu import obs
    me = threading.get_ident()
    return [r for r in obs.trace.records(since=mark) if r.tid == me]


def test_the_pool_stacks_each_kind_over_its_own_layers(hybrid_lm):
    from deeplearning4j_tpu import obs
    model, net = hybrid_lm
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=96)
    kv, state, tail = sched.pager.pool
    assert kv.shape == (2, 1 + 3 * 6, 16, 2, 32)        # 2 attention
    assert state.shape == (4, 1 + 3, 16, 128)           # 4 Mamba
    assert tail.shape == (4, 1 + 3, 3 * 160)
    assert sched.pager.cache.walks_kv
    per_slot = 2 * 4 * (4 * 16 * 128 + 4 * 3 * 160)
    assert sched.pager.state_bytes_per_slot == per_slot
    assert sched.pager.state_pool_bytes() == 4 * 4 * (
        4 * 16 * 128 + 4 * 3 * 160)
    assert (obs.metrics.SERVING_STATE_POOL.snapshot()[""]
            == sched.pager.state_pool_bytes())
    assert sched.pager.free_pages() == 18               # KV pages alone
    assert sched.pages_needed(20, 30) == 4              # 49 positions


def test_records_count_state_bytes_and_kv_pages_together(hybrid_lm):
    from deeplearning4j_tpu import obs
    model, net = hybrid_lm
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=96)
    mark, moved = obs.now(), obs.metrics.SERVING_STATE_MOVED.snapshot()[""]
    rng = np.random.default_rng(3)
    for t0 in (37, 9):
        assert sched.admit(_Req(rng.integers(0, 64, t0), 6))
    sched.step()
    recs = _own_records(mark)
    pre = [r.counts for r in recs if r.name == "serving.prefill"]
    assert [(c["chunks"], c["bucket"], c["t0"]) for c in pre] == [
        (3, 16, 37), (1, 16, 9)]
    step = [r for r in recs if r.name == "serving.decode_step"][-1]
    assert step.counts["active"] == 2
    assert step.counts["state_bytes"] == 2 * sched.pager.state_bytes_per_slot
    # positions 37 and 9 are being written: 3 pages and 1
    assert step.counts["kv_pages"] == 4
    assert (obs.metrics.SERVING_STATE_MOVED.snapshot()[""] - moved
            == 2 * sched.pager.state_bytes_per_slot)
    assert obs.metrics.SERVING_KV_WALKED.snapshot()[""] == 4


def test_snapshots_of_a_state_are_refused(hybrid_lm):
    model, net = hybrid_lm
    for kw in (dict(prefix_sharing=True), dict(spec_k=2)):
        with pytest.raises(ValueError, match="state snapshots"):
            DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=64, **kw)
    with pytest.raises(ValueError, match="do not apply"):
        kv_pager.KVPager(n_layers=1, n_kv_heads=2, head_dim=16, n_pages=4,
                         block=16, cache_quant="int8", ssm=(SPEC, 2))


def test_churn_leaks_no_page_of_either_kind(hybrid_lm):
    """Admit, finish, cancel and evict with a step in flight, over
    more sequences than slots: every KV page comes back, no stream is
    left open, every served token is dense ``generate()``'s (so a
    state page handed on started its next sequence from an empty
    state), and the trash state page aside nothing outside the live
    slots' pages moves."""
    model, net = hybrid_lm
    sched = DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=96)
    rng = np.random.default_rng(11)
    reqs = [_Req(rng.integers(0, 64, t0), n, stop)
            for t0, n, stop in ((21, 9, None), (7, 12, 4), (35, 5, None),
                                (17, 30, None), (40, 6, 3), (5, 8, None))]
    waiting, evicted = list(reqs), reqs[3]
    steps = 0
    while waiting or sched.active_count() or sched._inflight is not None:
        while waiting and sched.can_admit(waiting[0].prompt.size,
                                          waiting[0].max_new):
            assert sched.admit(waiting.pop(0))
        sched.step()
        steps += 1
        if (evicted is not None and len(evicted.tokens) >= 5
                and sched._inflight is not None):
            assert sched.evict(evicted)     # its row in flight is dropped
            n_evicted, evicted = len(reqs[3].tokens), None
        sched.pager.check_invariants()
    assert all(r.done for r in reqs) and steps < 200
    assert len(reqs[3].tokens) == n_evicted
    assert sched.pager.free_pages() == sched.pager.n_pages - 1
    for r in reqs:
        dense = np.asarray(model.generate(
            net, r.prompt[None], max(len(r.tokens), 1)))
        np.testing.assert_array_equal(
            r.tokens, dense[0, r.prompt.size:][:len(r.tokens)])


def test_a_state_page_handed_on_starts_empty(hybrid_lm, monkeypatch):
    """A slot's state page comes to its next sequence as the last one
    left it: after admission it holds what a fresh pool's would, bit
    for bit; with the reset left out it does not."""
    model, net = hybrid_lm
    rng = np.random.default_rng(12)
    first, second = (_Req(rng.integers(0, 64, t0), 6) for t0 in (30, 9))

    def admitted(after_first):
        sched = DecodeScheduler(model, net, max_slots=1, block=16,
                                max_context=96)
        if after_first:
            assert sched.admit(first)
            while sched.active_count() or sched._inflight is not None:
                sched.step()
            assert np.abs(np.asarray(sched.pager.pool[1][:, 1])).max() > 0
        second.tokens = []
        assert sched.admit(second)
        return [np.asarray(a[:, 1]) for a in sched.pager.pool[1:]]

    fresh = admitted(False)
    for a, b in zip(fresh, admitted(True)):
        np.testing.assert_array_equal(a, b)
    _state_not_emptied(monkeypatch)
    assert np.abs(admitted(True)[0] - fresh[0]).max() > 1e-3


def test_gateway_serves_the_hybrid_model(hybrid_lm):
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.serving import ServingGateway
    model, net = hybrid_lm
    gw = ServingGateway(model, net, max_slots=3, block=16, max_context=96)
    try:
        report = gw.warmup(prompt_lens=[5, 40])
        assert report["buckets"] == [16] and report["compiled"] <= 2
        before = sentry.total_traces()
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, 64, t).astype(np.int32)
                   for t in (5, 40, 17, 33, 9)]
        streams = [gw.submit(p, max_new=7) for p in prompts]
        outs = [np.asarray(s.result(timeout=120)) for s in streams]
        assert sentry.total_traces() == before      # nothing traced
        for p, out in zip(prompts, outs):
            dense = np.asarray(model.generate(net, p[None], 7))[0]
            np.testing.assert_array_equal(out, dense)
        assert gw.stats()["free_pages"] == gw._sched.pager.n_pages - 1
    finally:
        gw.shutdown(drain=False, timeout=30)


def test_the_step_s_feed_is_no_view_of_the_host_s_mirror(hybrid_lm):
    """On a CPU backend ``jnp.asarray`` may alias an aligned host
    array: a feed that did would change under a step launched ahead
    when a retirement zeroes its page-table row (the unsteady
    ``test_row_launched_ahead...[eos]`` of ``test_retention.py``)."""
    model, net = hybrid_lm
    sched = DecodeScheduler(model, net, max_slots=64, block=16,
                            max_context=96)
    assert sched.admit(_Req(np.arange(9) % 64, 8))
    sched.step()
    feed = sched._dev_feed
    before = {k: np.array(feed[k]) for k in ("pt", "temps")}
    sched._page_table[:] = 7
    sched._temps[:] = 3.0
    for k, was in before.items():
        np.testing.assert_array_equal(np.asarray(feed[k]), was)
    sched.drain()


# -- devtime scopes ------------------------------------------------------

def test_every_ssm_scope_is_in_the_lowered_programs(hybrid_lm):
    model, net = hybrid_lm
    sched = DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=64)
    params = model.decode_params(net)
    sds = jax.ShapeDtypeStruct
    pool = tuple(sds(a.shape, a.dtype) for a in sched.pager.pool)
    step = sched._step_fn.lower(params, pool,
                                *sched._step_feed_shapes()).as_text(
                                    debug_info=True)
    i32, f32 = jnp.int32, jnp.float32
    chunk = sched._chunk_fn.lower(
        params, pool, (), sched._chunk_where_shapes(), sds((1, 16), i32),
        sds((), i32), sds((), i32), sds((), f32), sds((), f32),
        sds((), i32)).as_text(debug_info=True)
    for li in SPEC.layers("mamba2"):
        at = f"dl4j.paged_decode.block_{li}.mixer/"
        assert at + "dl4j.ops.ssm_decode" in step
        assert at + "dl4j.ops.ssm_conv" in step
        at = f"dl4j.chunk_prefill.block_{li}.mixer/"
        assert at + "dl4j.ops.ssm_prefill" in chunk
        assert at + "dl4j.ops.ssm_prefill/dl4j.ops.ssm_conv" in chunk
    for li in SPEC.layers("softmax"):
        assert (f"dl4j.paged_decode.block_{li}.mixer/"
                "dl4j.ops.paged_decode_attention") in step
        assert f"dl4j.paged_decode.block_{li}.mixer/dl4j.ops.ssm" not in step

"""Latent attention (``ops/latent.py``) and the expert layer
(``ops/moe.py``) of the ``mixer="latent"`` decoder: the forms of each
against each other and against a literal ``numpy`` routing, the share
test of one chip's experts, the model's three forwards, and the
gateway's prefill-then-decode through the paged latent pool held
against the benchmark's plain reference.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.ops import latent as L
from deeplearning4j_tpu.ops import moe as M
from deeplearning4j_tpu.serving import DecodeScheduler
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SPEC = L.LatentSpec(q_rank=48, kv_rank=32, nope=16, rope=8, v=16,
                    yarn=(40.0, 64, 32.0, 1.0, 1.0, 1.0))
EXPERTS = M.ExpertSpec(width=32, n_held=4, n_routed=32, top_k=4,
                       n_group=4, topk_group=2, scale=2.5, n_shared=1,
                       offset=0, first_dense=1)
#: the benchmark's names for the same sizes (the reference reads these)
_TOY = dict(num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=10000.0, rms_norm_eps=1e-6,
            rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                          "beta_slow": 1, "mscale": 1,
                          "mscale_all_dim": 1,
                          "original_max_position_embeddings": 64},
            num_experts_per_tok=4, n_group=4, topk_group=2,
            routed_scaling_factor=2.5, n_routed_experts=4,
            expert_offset=0)


def _model(**kw):
    kw.setdefault("experts", EXPERTS)
    return CausalTransformerLM(
        vocab_size=64, hidden=64, n_layers=3, n_heads=4,
        max_len=kw.pop("max_len", 128), ffn_mult=2.5, mixer="latent",
        latent=SPEC, updater=kw.pop("updater", upd.Sgd(0.0)), seed=3,
        **kw)


@pytest.fixture(scope="module")
def latent_lm():
    model = _model()
    return model, model.init()


# -- ops/latent.py -------------------------------------------------------

def test_yarn_frequencies_ramp_between_the_two():
    plain = L.yarn_inv_freq(64, 1e4)
    np.testing.assert_allclose(plain, 1e4 ** (-np.arange(32) / 32),
                               rtol=1e-6)
    yarn = L.yarn_inv_freq(64, 1e4, (40.0, 4096, 32.0, 1.0, 1.0, 1.0))
    # fast frequencies keep their value, slow ones are divided by the
    # factor, and between them the blend falls monotonically
    np.testing.assert_allclose(yarn[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(yarn[-5:], plain[-5:] / 40, rtol=1e-6)
    ratio = yarn / plain
    assert np.all(np.diff(ratio) <= 1e-7) and ratio[16] < 1 < 40 * ratio[16]
    # 0.1 ln 40 + 1, squared, over root 192
    spec = L.LatentSpec(1536, 512, 128, 64, 128,
                        yarn=(40.0, 4096, 32.0, 1.0, 1.0, 1.0))
    assert L.softmax_scale(spec) == pytest.approx(
        (0.1 * np.log(40) + 1) ** 2 / np.sqrt(192))
    assert spec.row == 576


def test_rotation_pairs_adjacent_features_and_is_relative():
    x = jnp.arange(8.0)[None, :] + 1.0
    ang = jnp.full((1, 4), 0.3)
    y = np.asarray(L.rotate(x, ang))
    c, s = np.cos(0.3), np.sin(0.3)
    np.testing.assert_allclose(
        y[0, :2], [1 * c - 2 * s, 1 * s + 2 * c], rtol=1e-6)
    # a query at position p against a key at position j depends on
    # p - j alone
    rng = np.random.default_rng(0)
    q, k = rng.normal(size=(2, 1, 8)).astype(np.float32)
    freq = jnp.asarray(L.yarn_inv_freq(SPEC.rope, 1e4, SPEC.yarn))
    dot = lambda p, j: float(jnp.sum(L.rotate(q, p * freq[None])
                                     * L.rotate(k, j * freq[None])))
    assert dot(9.0, 4.0) == pytest.approx(dot(25.0, 20.0), rel=1e-4)


def _mha(key, f=64, h=4):
    from deeplearning4j_tpu.nn.layers.attention import LatentAttention
    layer = LatentAttention(n_in=f, n_heads=h, spec=SPEC)
    params, _, _ = layer.init(key, (24, f))
    return layer, params


def test_absorbed_form_equals_expanded_form():
    """A decode position read through stored latent rows (``W_kvb``'s
    halves folded into the query and the output) is the expanded
    form's last row."""
    layer, p = _mha(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64))
    want, _ = layer.apply(p, {}, h)
    q_nope, q_rope, row = L.project(p, h[0], SPEC, 4, 1e4,
                                    jnp.arange(24))
    for t in (0, 7, 23):
        o = L.attend_rows(
            L.absorb(p, q_nope[t:t + 1], q_rope[t:t + 1], SPEC),
            row[None], jnp.asarray([t + 1]), L.softmax_scale(SPEC),
            SPEC.kv_rank)
        got = L.unabsorb(p, o, SPEC) @ p["Wo"]
        np.testing.assert_allclose(got[0], want[0, t], atol=2e-5)


def test_expanded_form_equals_a_literal_softmax():
    """The padded widths and the folded scale change nothing: one head
    at a time, scores from K and V made of the latent, a plain causal
    softmax in numpy."""
    layer, p = _mha(jax.random.PRNGKey(2))
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 20, 64))
    got, _ = layer.apply(p, {}, h)
    q_nope, q_rope, row = (np.asarray(z, np.float64) for z in L.project(
        p, h.reshape(40, 64), SPEC, 4, 1e4, jnp.tile(jnp.arange(20), 2)))
    kv = (row[:, :32] @ np.asarray(p["Wkvb"], np.float64)).reshape(
        40, 4, 32)
    want = np.zeros((2, 20, 4, 16))
    for b in range(2):
        rows = slice(20 * b, 20 * b + 20)
        for head in range(4):
            s = (q_nope[rows, head] @ kv[rows, head, :16].T
                 + q_rope[rows, head] @ row[rows, 32:].T
                 ) * L.softmax_scale(SPEC)
            s = np.where(np.tril(np.ones((20, 20), bool)), s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            want[b, :, head] = (w / w.sum(-1, keepdims=True)
                                ) @ kv[rows, head, 16:]
    np.testing.assert_allclose(
        got, want.reshape(2, 20, 64) @ np.asarray(p["Wo"], np.float64),
        atol=2e-5)


# -- ops/moe.py ----------------------------------------------------------

def _numpy_route(s, bias, n_group, topk_group, top_k, scale):
    """The routing, literally: one row at a time, ties to the lower
    index."""
    ids, ws = [], []
    per = s.shape[1] // n_group
    for row in s:
        c = row + bias
        groups = [sorted(c[g * per:(g + 1) * per])[-2:] for g in
                  range(n_group)]
        score = [a + b for a, b in groups]
        kept = sorted(range(n_group), key=lambda g: (-score[g], g)
                      )[:topk_group]
        cand = [e for e in range(len(c)) if e // per in kept]
        chosen = sorted(cand, key=lambda e: (-c[e], e))[:top_k]
        w = np.asarray([row[e] for e in chosen], np.float64)
        ids.append(chosen)
        ws.append(w / w.sum() * scale)
    return np.asarray(ids), np.asarray(ws)


@pytest.mark.parametrize("case", ["random", "bias", "ties"])
def test_route_equals_a_literal_numpy_routing(case):
    rng = np.random.default_rng(7)
    h = rng.normal(size=(40, 64)).astype(np.float32)
    w_r = (rng.normal(size=(64, 32)) / 8).astype(np.float32)
    bias = np.zeros(32, np.float32)
    if case == "bias":      # the choice goes by s + b, the weight by s
        bias = rng.normal(size=32).astype(np.float32) * 0.3
    if case == "ties":      # equal columns: equal scores, lower index
        w_r[:, 9] = w_r[:, 3]
        w_r[:, 20] = w_r[:, 17]
        w_r[:, 8:16] = w_r[:, 0:8]      # two groups level
    ids, w = M.route(jnp.asarray(h), jnp.asarray(w_r), jnp.asarray(bias),
                     n_group=4, topk_group=2, top_k=4, scale=2.5)
    s = 1 / (1 + np.exp(-(h.astype(np.float64) @ w_r)))
    if case == "ties":
        s[:, 9], s[:, 20], s[:, 8:16] = s[:, 3], s[:, 17], s[:, 0:8]
    want_ids, want_w = _numpy_route(s.astype(np.float32), bias, 4, 2, 4,
                                    2.5)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=2e-5)
    assert np.asarray(w).sum(-1) == pytest.approx(2.5, rel=1e-5)


def _moe_params(key, f=64, e=EXPERTS, n_held=None, dtype=jnp.float32):
    n_held = e.n_held if n_held is None else n_held
    ks = jax.random.split(key, 7)
    n = lambda k, *shape: (jax.random.normal(k, shape) / np.sqrt(
        shape[-2])).astype(dtype)
    return {"Wr": n(ks[0], f, e.n_routed).astype(jnp.float32),
            "br": jnp.zeros((e.n_routed,), jnp.float32),
            "Weg": n(ks[1], n_held, f, e.width),
            "Weu": n(ks[2], n_held, f, e.width),
            "Wed": n(ks[3], n_held, e.width, f),
            "Wsg": n(ks[4], f, e.width), "Wsu": n(ks[5], f, e.width),
            "Wsd": n(ks[6], e.width, f)}


@pytest.mark.parametrize("rows", [5, 64, 700])
def test_sorted_experts_equal_the_plain_form(rows):
    """Pairs sorted by expert and multiplied a tile at a time give what
    every held expert on every row, masked by the routing, gives; the
    counts are the routing's own."""
    p = _moe_params(jax.random.PRNGKey(0))
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, 64))
    ids, w = M.route(h, p["Wr"], p["br"], n_group=4, topk_group=2,
                     top_k=4, scale=2.5)
    got, counts = jax.jit(M.experts, static_argnums=4)(h, p, ids, w,
                                                       (0, 4))
    want, plain_counts = M.experts_plain(h, p, ids, w, (0, 4))
    np.testing.assert_allclose(got, want, atol=2e-5)
    want_counts = [(np.asarray(ids) == e).sum() for e in range(4)]
    np.testing.assert_array_equal(counts, want_counts)
    np.testing.assert_array_equal(plain_counts, want_counts)


@pytest.mark.parametrize("width,dtype", [
    (32, jnp.float32), (128, jnp.float16)],
    ids=["not_lane_aligned", "a_dtype_the_kernel_does_not_take"])
def test_the_expert_layers_here_keep_the_loop(monkeypatch, width, dtype):
    """The rule that engages the expert kernel reads the operands
    alone. Even with the kernels forced, this model's test widths (64
    wide rows, 32 wide experts) are not whole lane tiles and keep the
    tile loop, as does a compute dtype the kernel does not take; the
    traced layers are tallied as ``loop``, none as ``kernel``. (An
    expert of the PUBLISHED widths is too large to lie in VMEM twice:
    ``tests/test_tpu_aot_compile.py``.)"""
    from deeplearning4j_tpu import obs
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    e = M.ExpertSpec(width=width, n_held=4, n_routed=32, top_k=4,
                     n_group=4, topk_group=2, scale=2.5, n_shared=1)
    f = 64 if width == 32 else 128
    p = _moe_params(jax.random.PRNGKey(0), f=f, e=e, dtype=dtype)
    h = jax.random.normal(jax.random.PRNGKey(1), (9, f)).astype(dtype)
    assert not M._use_expert_kernel(h, p)
    tally = lambda: dict(obs.metrics.MOE_EXPERT_LAYERS.snapshot())
    before = tally()
    text = jax.jit(lambda p, h: M.layer(p, h, e)).lower(p, h).as_text()
    assert "stablehlo.while" in text
    after = tally()
    assert after['{path="loop"}'] == before.get('{path="loop"}', 0) + 1
    assert after.get('{path="kernel"}', 0) == before.get(
        '{path="kernel"}', 0)


def test_no_token_is_dropped_when_every_row_chooses_one_expert():
    """The worst routing for a capacity: all 300 rows send a pair to
    held expert 2 (and their others elsewhere). Every row gets its
    expert's output; nothing is capped."""
    p = _moe_params(jax.random.PRNGKey(1))
    h = jax.random.normal(jax.random.PRNGKey(2), (300, 64))
    ids = jnp.tile(jnp.asarray([[2, 9, 17, 25]], jnp.int32), (300, 1))
    w = jnp.full((300, 4), 0.625)
    got, counts = M.experts(h, p, ids, w, (0, 4))
    np.testing.assert_array_equal(counts, [0, 0, 300, 0])
    want = 0.625 * M.gated(h, p["Weg"][2], p["Weu"][2], p["Wed"][2])
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.min(jnp.max(jnp.abs(got), axis=1))) > 0


@pytest.mark.parametrize("plain", [False, True])
def test_rows_without_a_token_make_no_pair(plain):
    """A bucket's padding and a slot without a sequence all hold ONE
    token and would all choose the same experts (whole tiles for rows
    nobody reads, more or fewer by the luck of that token's route):
    under ``live`` they make no pair, and the live rows get what they
    get alone. A dead row keeps the shared expert's part only."""
    p = _moe_params(jax.random.PRNGKey(4))
    h = jax.random.normal(jax.random.PRNGKey(5), (7, 64))
    ids, _ = M.route(h, p["Wr"], p["br"], n_group=4, topk_group=2,
                     top_k=4, scale=2.5)
    most = int(jnp.argmax(jnp.sum(ids < 4, axis=1)))
    pad = jnp.tile(h[most][None], (9, 1))   # nine times one token
    rows = jnp.concatenate([h, pad])
    live = jnp.arange(16) < 7
    alone, want = M.layer(p, h, EXPERTS, plain=plain)
    got, counts = jax.jit(
        lambda r, m: M.layer(p, r, EXPERTS, plain=plain, live=m))(
            rows, live)
    np.testing.assert_array_equal(counts, want)
    np.testing.assert_allclose(got[:7], alone, atol=2e-5)
    np.testing.assert_allclose(
        got[7:], M.gated(pad, p["Wsg"], p["Wsu"], p["Wsd"]), atol=2e-5)
    # routed all the same, the padding alone would have made pairs
    _, routed = M.layer(p, rows, EXPERTS, plain=plain)
    assert int(routed.sum()) >= int(want.sum()) + 9
    # the mask keeps its rows' shape: [B, T] as a prefill has them
    _, folded = M.layer(p, rows.reshape(2, 8, 64), EXPERTS, plain=plain,
                        live=live.reshape(2, 8))
    np.testing.assert_array_equal(folded, want)


def test_the_shares_of_all_chips_add_up_to_the_whole_layer():
    """The guide's share test: 8 chips hold 4 experts each of 32. The
    routed parts of all 8 shares, plus the shared expert once, add up
    to what the uncut reference gives for the layer with all 32."""
    from benchmarks.reference import latent_moe_lm as ref
    whole = _moe_params(jax.random.PRNGKey(5), n_held=32)
    h = jax.random.normal(jax.random.PRNGKey(6), (48, 64))
    d = dict(top_k=4, n_group=4, topk_group=2, scale=2.5, n_held=32,
             offset=0)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts_ffn(whole, h, d, "float32")
    shared = M.gated(h, whole["Wsg"], whole["Wsu"], whole["Wsd"])
    total, pairs = shared, 0
    for rank in range(8):
        spec = M.ExpertSpec(width=32, n_held=4, n_routed=32, top_k=4,
                            n_group=4, topk_group=2, scale=2.5,
                            offset=4 * rank)
        mine = dict(whole, **{k: whole[k][4 * rank:4 * rank + 4]
                              for k in ("Weg", "Weu", "Wed")})
        y, counts = M.layer(mine, h, spec)
        total = total + (y - shared)    # every chip computes the shared
        pairs += int(counts.sum())
        # and one chip's share is the reference's with the same share
        if rank == 3:
            with jax.default_matmul_precision("highest"):
                part, _ = ref.experts_ffn(mine, h, dict(
                    d, n_held=4, offset=12), "float32")
            np.testing.assert_allclose(y, part, atol=3e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert pairs == 48 * 4      # every pair computed once, somewhere


def test_expert_spec_is_checked():
    with pytest.raises(ValueError, match="published"):
        M.ExpertSpec(width=8, n_held=4, n_routed=16, top_k=2, offset=14)
    with pytest.raises(ValueError, match="n_group"):
        M.ExpertSpec(width=8, n_held=4, n_routed=30, top_k=2, n_group=4)
    assert M.ExpertSpec.of(EXPERTS.to_dict()) == EXPERTS
    assert L.LatentSpec.of(
        {**SPEC.to_dict(), "yarn": list(SPEC.yarn)}) == SPEC


# -- the model's forwards ------------------------------------------------

def test_mixer_arguments_are_checked():
    with pytest.raises(ValueError, match="come together"):
        CausalTransformerLM(mixer="latent")
    with pytest.raises(ValueError, match="come together"):
        CausalTransformerLM(latent=SPEC)
    with pytest.raises(ValueError, match="cache_quant"):
        _model(cache_quant="int8")
    with pytest.raises(ValueError, match="router"):
        CausalTransformerLM(experts=EXPERTS, serve_quant="int8")
    model = _model()
    for name, kw in (("prefix_sharing", {"prefix_sharing": True}),
                     ("spec_k", {"spec_k": 2})):
        with pytest.raises(ValueError, match=name):
            DecodeScheduler(model, None, max_slots=2, block=16,
                            max_context=64, **kw)


def test_the_block_serializes_with_its_specs(latent_lm):
    from deeplearning4j_tpu.nn.layers.base import layer_from_dict
    model, net = latent_lm
    blocks = [l for l in net.conf.layers
              if type(l).__name__ == "TransformerDecoderBlock"]
    assert [b.ffn for b in blocks] == ["dense", "experts", "experts"]
    assert "moe" not in net.params["layer_1"]
    assert set(net.params["layer_2"]["moe"]) == {
        "Wr", "br", "Weg", "Weu", "Wed", "Wsg", "Wsu", "Wsd"}
    back = layer_from_dict(blocks[1].to_dict())
    assert L.LatentSpec.of(back.latent) == SPEC
    assert M.ExpertSpec.of(back.experts) == EXPERTS


def test_generate_equals_the_training_forward(latent_lm):
    """Dense ``generate()`` (expanded prefill into one latent array a
    layer, then the absorbed form over it, experts by the sorted form)
    picks at every position the training forward's (expanded, plain
    experts) best token."""
    model, net = latent_lm
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, (2, 11)).astype(np.int32)
    out = np.asarray(model.generate(net, prompt, 9))
    logits = np.asarray(net.output(out[:, :-1]))
    np.testing.assert_array_equal(logits.argmax(-1)[:, 10:], out[:, 11:])


def test_fit_trains_the_latent_expert_model():
    model = _model(updater=upd.Adam(learning_rate=3e-3), max_len=32)
    net = model.init(16)
    rng = np.random.default_rng(1)
    x = rng.integers(0, 64, (8, 16)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    before = jax.tree.map(np.asarray, net.params)
    first = None
    for _ in range(12):
        net.fit(x, y)
        first = first if first is not None else float(net.score())
    assert float(net.score()) < first
    moved = jax.tree.map(lambda a, b: bool(np.any(a != np.asarray(b))),
                         before, net.params)
    # every matrix learns: the latents', the router, the experts held
    assert all(moved["layer_2"]["mha"][k] for k in ("Wqa", "Wkva",
                                                    "Wkvb"))
    assert all(moved["layer_2"]["moe"][k]
               for k in ("Wr", "Weg", "Wsd"))


def test_the_router_stays_float32_under_a_bf16_compute_dtype():
    model = _model(compute_dtype="bfloat16")
    net = model.init()
    served = model.decode_params(net)
    moe = served["layer_2"]["moe"]
    assert moe["Wr"].dtype == moe["br"].dtype == jnp.float32
    assert moe["Weg"].dtype == served["layer_1"]["Wg"].dtype == jnp.bfloat16
    # leaves already in the compute dtype, the router in float32, are
    # served as they are: no second copy
    net.params = served
    assert model.decode_params(net) is served


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


def _served_logits(model, net, seq, t0, dtype=None):
    """Teacher-forced logits of ``seq[t0 - 1:]`` by the gateway's own
    programs: the bucket prefill into the sequence's latent pages,
    then THE paged block a position at a time over the pool."""
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=96)
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
    return req.tokens[0], np.stack(rows)


def _reference_logits(params, seq, t0, rows, faults=()):
    from benchmarks.reference import latent_moe_lm as ref
    with jax.default_matmul_precision("highest"):
        logits, margin = ref.logits_from(
            params, jnp.asarray(seq), t0 - 1, d=ref.dims(_TOY),
            rows=rows, faults=faults)
    return np.asarray(logits), np.asarray(margin)


#: float32 on the CPU, logits up to 4 in size: the bucket prefill's
#: expanded form and the decode's absorbed form differ from the
#: reference's one expanded pass in the order of float32 sums (read:
#: 4e-6 at most over the three prompts); a latent stored without its
#: norm moves a logit by 0.1 or more
LOGIT_TOL = 5e-5


@pytest.mark.parametrize("t0", [16, 23, 41])
def test_prefill_then_paged_decode_matches_the_reference_logits(
        latent_lm, t0):
    """Prompts of a bucket exactly, of a page and a part, of two pages
    and a part: the latent rows written at admission and at every
    decoded position are the reference's, by the logits they give; no
    compared position lies at a routing tie."""
    model, net = latent_lm
    rng = np.random.default_rng(t0)
    seq = rng.integers(0, 64, t0 + 12).astype(np.int32)
    first, got = _served_logits(model, net, seq, t0)
    want, margin = _reference_logits(net.params, seq, t0, 13)
    assert margin.min() > 1e-4
    assert first == int(want[0].argmax())
    assert np.abs(got - want[1:]).max() < LOGIT_TOL
    for fault in ("raw_latent", "drop_route"):
        other, _ = _reference_logits(net.params, seq, t0, 13, (fault,))
        assert np.abs(got - other[1:]).max() > 100 * LOGIT_TOL, fault


def test_bucket_prefill_by_the_flash_kernel_equals_the_einsum_path(
        latent_lm, admits_alike_by_einsum_and_kernel):
    """The expanded form's keys (48 wide here, padded to one 128-lane
    tile) of every head through the kernel, the length beside them."""
    admits_alike_by_einsum_and_kernel(*latent_lm, block=16,
                                      max_context=128)


def test_bf16_serving_stays_within_bf16_of_the_reference():
    """The same comparison in the compute dtype the cell serves in:
    weights kept as their bf16 rounding (the router in float32), the
    pool in bf16. Away from routing ties the logits lie within a few
    bf16 ulps of a value near 4 (2^-6 each); read: 0.05 at most."""
    model = _model(compute_dtype="bfloat16")
    net = model.init()
    net.params = model.decode_params(net)
    seq = np.random.default_rng(3).integers(0, 64, 40).astype(np.int32)
    _, got = _served_logits(model, net, seq, 23)
    want, margin = _reference_logits(net.params, seq, 23, 18)
    clear = margin[1:] > 0.02
    assert clear.sum() >= 10
    assert np.abs(got - want[1:])[clear].max() < 0.12
    assert np.abs(got - want[1:])[clear].max() > LOGIT_TOL


def test_paged_latent_writes_its_own_pages_only(latent_lm):
    """A decode step over two live slots and one empty one: the pages
    of the positions written change and the free pages come out bit
    for bit (the empty slot's row goes to the trash page, as every
    pool's does)."""
    model, net = latent_lm
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=96)
    a, b = _Req(np.arange(20) % 64, 8), _Req(np.arange(7) % 64, 8)
    assert sched.admit(a) and sched.admit(b)
    assert sched.pager.pool[0].shape == (3, 19, 16, L.lanes(40))
    before = np.asarray(sched.pager.pool[0])
    assert not before[:, 0].any()           # prefill wrote no trash
    sched.step()
    sched.drain()
    after = np.asarray(sched.pager.pool[0])
    changed = {int(p) for p in np.nonzero(
        (before != after).any(axis=(0, 2, 3)))[0]}
    assert changed == {0, sched.pager.owned(a)[1],
                       sched.pager.owned(b)[0]}
    # a row is [c_kv | k_rope | zeros]: the stored tail stays zero
    assert not after[..., SPEC.row:].any() and after[..., :SPEC.row].any()


def test_records_count_latent_rows_and_expert_pairs(latent_lm,
                                                    monkeypatch):
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    model, net = latent_lm
    # the walk's items are counted by the kernel's own chunk: 2 pages
    monkeypatch.setattr(pk, "_LATENT_CHUNK_ROWS", 32)
    sched = DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=96)
    mark = obs.now()
    pairs = obs.metrics.SERVING_EXPERT_PAIRS.snapshot()[""]
    rows = obs.metrics.SERVING_LATENT_ROWS.snapshot()[""]
    for t in (40, 7):
        assert sched.admit(_Req(np.arange(t) % 64, 6))
    sched.step()        # launches; nothing to read yet
    sched.step()
    recs = [r for r in obs.trace.records(since=mark)
            if r.tid == __import__("threading").get_ident()]
    prefills = [r for r in recs if r.name == "serving.prefill"]
    # 2 expert layers, 4 a token: of the prompt's rows, not the bucket's
    assert [r.counts["bucket"] for r in prefills] == [64, 16]
    assert all(0 < r.counts["expert_pairs"] <= 2 * r.counts["t0"] * 4
               for r in prefills)
    for r in prefills:      # what the prompt's rows alone route
        alone = []
        di.stack(net.params, jnp.arange(r.counts["t0"])[None] % 64, model,
                 di.latent_prefill(model, lambda li, rows: None), "x",
                 counts=alone)
        assert r.counts["expert_pairs"] == int(sum(c.sum() for c in alone))
    first, second = [r for r in recs if r.name == "serving.decode_step"]
    assert first.counts["ahead"] == 0 and first.counts["expert_pairs"] == 0
    # the slots' lengths and the position each step writes
    assert first.counts["latent_rows"] == 41 + 8
    assert second.counts["latent_rows"] == 42 + 9
    # 41 and 8 rows in chunks of 32: (2 + 1) items a layer's walk, of
    # which all but the first are issued ahead; then 42 and 9
    assert first.counts["latent_chunks"] == 2 + 1
    assert second.counts["latent_chunks"] == 2 + 1
    assert pk.latent_chunk_pages(16, 96 // 16) == 2
    c = second.counts
    assert c["ahead"] == 1 and c["kv_pages"] == c["state_bytes"] == 0
    # 2 rows x 2 expert layers, 4 a token of which some are held here
    assert 0 < c["experts_hit"] <= c["expert_pairs"] <= 2 * 2 * 4
    assert 0 < c["expert_pairs_max"] <= c["expert_pairs"]
    assert (obs.metrics.SERVING_EXPERT_PAIRS.snapshot()[""] - pairs
            == c["expert_pairs"] + sum(r.counts["expert_pairs"]
                                       for r in prefills))
    assert (obs.metrics.SERVING_LATENT_ROWS.snapshot()[""] - rows
            == 49 + 51)


@pytest.mark.parametrize("ends_by", ["eos", "budget"])
def test_step_in_flight_discards_an_ended_row(latent_lm, ends_by):
    """The step launched before a sequence's last tokens are read: one
    that ends by ``eos_id`` has a row in it, computed and discarded;
    its pages go back, another sequence takes them, and every stream
    holds what dense ``generate()`` gives. Admit, retire and evict
    leave the pager's invariants whole."""
    model, net = latent_lm
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=96)
    rng = np.random.default_rng(4)
    x = (_Req(rng.integers(0, 64, 21), 20, stop_at=4)
         if ends_by == "eos" else _Req(rng.integers(0, 64, 21), 4))
    nb = _Req(rng.integers(0, 64, 7), 12)
    assert sched.admit(x) and sched.admit(nb)
    sched.pager.check_invariants()
    for _ in range(4):
        sched.step()
    assert x.done and len(x.tokens) == 4 and len(nb.tokens) == 4
    assert sched._inflight is not None and not sched.pager.owned(x)
    sched.pager.check_invariants()
    y, gone = _Req(rng.integers(0, 64, 33), 6), None
    assert sched.admit(y)
    if ends_by == "budget":
        gone = _Req(rng.integers(0, 64, 9), 30)
        assert sched.admit(gone)
        sched.step()
        assert sched.evict(gone) and gone.done
        sched.pager.check_invariants()
    while sched.active_count() or sched._inflight is not None:
        sched.step()
    for r, n in ((y, 6), (nb, 12), (x, 4)):
        dense = np.asarray(model.generate(net, r.prompt[None], n))
        np.testing.assert_array_equal(r.tokens, dense[0, r.prompt.size:])
    sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1


def test_gateway_serves_the_latent_expert_model(latent_lm):
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.serving import ServingGateway
    model, net = latent_lm
    gw = ServingGateway(model, net, max_slots=3, block=16,
                        max_context=96)
    try:
        gw.warmup(prompt_lens=[5, 19, 33])
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 64, t).astype(np.int32)
                   for t in (5, 19, 33, 12)]
        dense = [np.asarray(model.generate(net, p[None], 7))[0]
                 for p in prompts]
        traces = sentry.total_traces()
        streams = [gw.submit(p, max_new=7) for p in prompts]
        for want, st in zip(dense, streams):
            np.testing.assert_array_equal(st.result(timeout=120), want)
        assert sentry.total_traces() == traces
    finally:
        gw.shutdown()
    assert gw.stats()["free_pages"] == 3 * 6

"""A decoder whose two kinds of softmax layer differ in more than
their window: full layers of 6 heads whose first 8 of 16 features turn
by YaRN frequencies beside window layers of 8 heads turned whole by
plain frequencies, a sigmoid gate a head in front of ``W_o``, and,
after one leading dense layer, 2-of-16 small SwiGLU experts beside a
shared one (``CausalTransformerLM(window=..., heads_by_layer=...,
rope_by_kind=..., attn_gate=True, experts=ExpertSpec(score=
"softmax_topk", scale=2.5, n_shared=1, first_dense=1))``): the rotary
rule against the rule written out, the expert layer's shared expert
under ``softmax_topk`` against its plain form and the benchmark's
plain reference, the shares of four chips against the whole layer, the
model's forwards, and the gateway's prefill-then-decode through the
ring held against the reference's full forward AT THE LOGITS.

Toy widths: hidden 64, heads 6 and 8 of 16 over 2 KV heads, window 32,
block 8 (a ring of 5 pages), contexts to 100 so that a ring wraps
twice in DECODE; 16 experts, 2 a token, one shared, one dense layer.
"""
import math
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
from deeplearning4j_tpu.ops.rotary import RopeRule, yarn_inv_freq
from deeplearning4j_tpu.serving import DecodeScheduler, ServingGateway
from deeplearning4j_tpu.serving import kv_pager
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# the gateway's own programs, a position at a time (block 8, three
# slots, ours not the first): the windowed decoder's tests' harness
from test_window_moe import _served_logits  # noqa: E402

WINDOW, BLOCK = 32, 8
KINDS = ["full", "window", "window", "window", "full"]
HEADS = [6, 8, 8, 8, 6]
EXPERTS = M.ExpertSpec(width=24, n_held=16, n_routed=16, top_k=2,
                       scale=2.5, n_shared=1, first_dense=1,
                       score="softmax_topk", unit="swiglu")
YARN = (4.0, 16.0, 4.0, 1.0)
FACTOR = 0.1 * math.log(4.0) + 1.0
RULES = {"full": RopeRule(theta=1e4, rotary_dim=8, yarn=YARN,
                          factor=FACTOR),
         "window": RopeRule(theta=1e4, rotary_dim=16)}
#: the benchmark's names for the same sizes (the reference reads these)
_TOY = dict(
    num_hidden_layers=5, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, hidden_size=64, intermediate_size=96,
    attention_bias=False, tie_word_embeddings=False, gating=True,
    moe_apply_router_weight_on_input=False, sliding_window=WINDOW,
    rms_norm_eps=1e-6, num_experts=16, num_experts_per_tok=2,
    moe_intermediate_size=24, shared_expert_intermediate_size=24,
    moe_routed_scaling_factor=2.5,
    layer_types=[{"full": "full_attention",
                  "window": "sliding_attention"}[k] for k in KINDS],
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    num_attention_heads_per_layer=HEADS,
    rope_parameters={
        "full_attention": {
            "rope_theta": 1e4, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": FACTOR,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 1e4,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 16},
    assumed={"gating": "per-head", "routing": "softmax_topk",
             "rotary_pairing": "half-split", "window_counts_own": True})


def _model(**kw):
    kw.setdefault("updater", upd.Sgd(learning_rate=0.05))
    return CausalTransformerLM(
        vocab_size=64, hidden=64, n_layers=5, n_heads=6, n_kv_heads=2,
        head_dim=16, ffn_mult=1.5, max_len=128, rope_theta=None,
        norm_eps=1e-6, window=WINDOW,
        window_layers=[i for i, k in enumerate(KINDS) if k == "window"],
        heads_by_layer=HEADS, rope_by_kind=RULES, attn_gate=True,
        experts=EXPERTS, seed=11, **kw)


@pytest.fixture(scope="module")
def gated_lm():
    model = _model()
    net = model.init(seq_len=64)
    # gains off their initial values, so that a term left out shows
    key = jax.random.PRNGKey(2)
    flat, treedef = jax.tree_util.tree_flatten(net.params)
    net.params = jax.tree_util.tree_unflatten(treedef, [
        a + 0.05 * jax.random.normal(jax.random.fold_in(key, i), a.shape,
                                     a.dtype)
        if a.ndim == 1 and a.shape[0] != 16 else a
        for i, a in enumerate(flat)])
    for i in range(1, 6):       # the published model has no biases
        net.params[f"layer_{i}"]["mha"]["bo"] = jnp.zeros((64,))
        if i > 1:
            net.params[f"layer_{i}"]["moe"]["br"] = jnp.zeros((16,))
    return model, net


# -- the rotary rule ---------------------------------------------------------

def _rule_written_out(x, pos, theta, rotary_dim, yarn, factor):
    """The published rule, literally, in float64: feature ``i`` of the
    first ``rotary_dim`` turns with ``i + rotary_dim / 2``."""
    x = np.asarray(x, np.float64)
    half = rotary_dim // 2
    base = theta ** (2.0 * np.arange(half) / rotary_dim)
    inv = 1.0 / base
    if yarn is not None:
        scale, original, fast, slow = yarn
        at = lambda turns: rotary_dim * math.log(
            original / (turns * 2 * math.pi)) / (2 * math.log(theta))
        low = max(math.floor(at(fast)), 0)
        high = min(math.ceil(at(slow)), rotary_dim - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3),
                       0, 1)
        inv = (1 - ramp) / base + ramp / (scale * base)
    out = x.copy()
    for t, p in enumerate(pos):
        c, s = np.cos(p * inv) * factor, np.sin(p * inv) * factor
        a, b = x[:, t, :, :half], x[:, t, :, half:rotary_dim]
        out[:, t, :, :half] = a * c - b * s
        out[:, t, :, half:rotary_dim] = a * s + b * c
    return out


@pytest.mark.parametrize("kind", sorted(RULES))
def test_rotary_embedding_by_a_rule_against_the_rule_written_out(kind):
    rule = RULES[kind]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))
    got = A.rotary_embedding(
        x, rule.theta, offset=5, rotary_dim=rule.rotary_dim,
        inv_freq=rule.inv_freq(16), factor=rule.factor)
    want = _rule_written_out(x, 5 + np.arange(9), rule.theta,
                             rule.rotary_dim, rule.yarn, rule.factor)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    # the features past the rotated width are the input's own
    np.testing.assert_array_equal(np.asarray(got[..., rule.rotary_dim:]),
                                  np.asarray(x[..., rule.rotary_dim:]))
    # a row a position (the decode step's form) gives the same turns
    rows = di.rotary_rows(x[0], rule, 5 + jnp.arange(9))
    np.testing.assert_allclose(np.asarray(rows), want[0], atol=2e-6)


def test_the_plain_rule_is_the_rotation_the_zoo_always_had():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 7, 2, 16))
    plain = A.rotary_embedding(x, 1e4, offset=3)
    by_rule = A.rotary_embedding(x, 1e4, offset=3, rotary_dim=16)
    np.testing.assert_allclose(np.asarray(by_rule), np.asarray(plain),
                               atol=1e-6)
    assert A.rotary_embedding(x, None, rotary_dim=8) is x


def test_yarn_frequencies_are_bit_equal_after_their_move():
    """``ops.latent.yarn_inv_freq(spec, theta)`` as the commit before
    this one had it, literally, against ``ops.rotary.yarn_inv_freq``
    for the DeepSeek configuration's spec (and a plain one)."""
    def before(rope, theta, yarn):
        dim = rope
        pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        if yarn is None:
            return (1.0 / pos_freqs).astype(np.float32)
        factor, original, beta_fast, beta_slow = yarn[:4]

        def correction_dim(turns):
            return dim * math.log(original / (turns * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(correction_dim(beta_fast)), 0)
        high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        keep = 1.0 - ramp
        inv = (1.0 / (factor * pos_freqs)) * (1.0 - keep) + (
            1.0 / pos_freqs) * keep
        return inv.astype(np.float32)

    deepseek = (40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    for rope, theta, yarn in ((64, 1e4, deepseek), (64, 1e4, None),
                              (64, 5e5, (64.0, 4096.0, 64.0, 1.0))):
        np.testing.assert_array_equal(yarn_inv_freq(rope, theta, yarn),
                                      before(rope, theta, yarn))
    from deeplearning4j_tpu.ops import latent
    assert latent.yarn_inv_freq is yarn_inv_freq


def test_laguna_s_full_layers_keep_fast_turns_and_stretch_slow_ones():
    """The published full-layer rule (theta 5e5, 64 rotated features,
    factor 64 over 4,096 original positions, beta 64 and 1)."""
    rule = RopeRule(theta=5e5, rotary_dim=64,
                    yarn=(64.0, 4096.0, 64.0, 1.0),
                    factor=1.4158883083359672)
    inv, plain = rule.inv_freq(128), yarn_inv_freq(64, 5e5)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-6)
    np.testing.assert_allclose(inv[-8:], plain[-8:] / 64, rtol=1e-6)
    assert np.all(np.diff(inv / plain) <= 1e-7)
    assert rule.factor == pytest.approx(0.1 * math.log(64) + 1)
    assert RopeRule.of(rule.to_dict()) == rule


# -- the expert layer: a shared expert under softmax_topk --------------------

def _moe_params(key, n_held=16, f=64, w=24, n_routed=16):
    ks = jax.random.split(key, 7)
    n = lambda k, s: jax.random.normal(k, s) / np.sqrt(s[-2])
    return {"Wr": n(ks[0], (f, n_routed)), "br": jnp.zeros((n_routed,)),
            "Weg": n(ks[1], (n_held, f, w)), "Weu": n(ks[2], (n_held, f, w)),
            "Wed": n(ks[3], (n_held, w, f)), "Wsg": n(ks[4], (f, w)),
            "Wsu": n(ks[5], (f, w)), "Wsd": n(ks[6], (w, f))}


def _reference_layer(p, b, faults=()):
    """The reference's sparse feed-forward ALONE over rows ``b``
    (already normed): ``ln2`` at unit gain, so ``experts_half`` sees
    ``b`` up to the norm's rescaling, which the test undoes."""
    from benchmarks.reference import gated_window_moe_lm as ref
    d = dict(eps=0.0, top_k=2, score="softmax_topk", scale=2.5)
    norm = np.sqrt(np.mean(np.square(np.asarray(b)), -1, keepdims=True))
    unit = jnp.asarray(np.asarray(b) / norm)       # rows of unit rms
    with jax.default_matmul_precision("highest"):
        out, margin = ref.experts_half(
            {"ln2": {"gamma": jnp.ones((b.shape[-1],))}, "moe": p}, unit,
            b.shape[0], d, "float32", faults)
    return np.asarray(out - unit), np.asarray(margin)


@pytest.mark.parametrize("rows", [1, 7, 48, 300])
def test_softmax_topk_with_a_shared_expert_against_plain_and_reference(
        rows):
    p = _moe_params(jax.random.PRNGKey(3))
    h = jax.random.normal(jax.random.PRNGKey(rows), (rows, 64))
    h = h / jnp.sqrt(jnp.mean(jnp.square(h), -1, keepdims=True))
    with jax.default_matmul_precision("highest"):
        y, counts = M.layer(p, h, EXPERTS)
        y_plain, counts_plain = M.layer(p, h, EXPERTS, plain=True)
    want, _ = _reference_layer(p, h)
    np.testing.assert_allclose(np.asarray(y), want, atol=3e-5)
    np.testing.assert_allclose(np.asarray(y_plain), want, atol=3e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(counts_plain))
    assert int(counts.sum()) == 2 * rows
    for fault in ("no_shared", "no_scale", "scale_on_shared",
                  "no_renorm", "sigmoid_scores", "drop_route"):
        other, _ = _reference_layer(p, h, (fault,))
        assert np.abs(np.asarray(y) - other).max() > 1e-2, fault


def test_the_shares_of_four_chips_add_up_to_the_uncut_layer():
    """Four chips of four experts each, the shared expert counted
    once: the routed parts the shares give, with what every chip
    computes alike, add up to the whole layer and to the reference."""
    p = _moe_params(jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(6), (40, 64))
    h = h / jnp.sqrt(jnp.mean(jnp.square(h), -1, keepdims=True))
    shared = M.gated(h, p["Wsg"], p["Wsu"], p["Wsd"])
    with jax.default_matmul_precision("highest"):
        whole, counts = M.layer(p, h, EXPERTS)
        parts, held = [], []
        for rank in range(4):
            spec = M.ExpertSpec(**{**EXPERTS.to_dict(), "n_held": 4,
                                   "offset": 4 * rank})
            mine = {**p, **{k: p[k][4 * rank:4 * rank + 4]
                            for k in ("Weg", "Weu", "Wed")}}
            y, c = M.layer(mine, h, spec)
            parts.append(y - shared)        # its routed part alone
            held.append(c)
    total = sum(parts) + shared             # the shared expert ONCE
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=3e-5)
    np.testing.assert_array_equal(np.concatenate(held),
                                  np.asarray(counts))
    want, _ = _reference_layer(p, h)
    np.testing.assert_allclose(np.asarray(total), want, atol=3e-5)


# -- the model's arguments ---------------------------------------------------

def test_a_layer_reads_its_own_heads_and_its_kind_s_rule():
    model = _model()
    assert [di.layer_heads(model, i) for i in range(5)] == HEADS
    assert di.layer_heads(model) == 6
    assert [di.layer_theta(model, i) for i in range(5)] == [
        RULES[k] for k in KINDS]
    assert [di.layer_window(model, i) for i in range(5)] == [
        None, 32, 32, 32, None]
    net = model.init(seq_len=16)
    shapes = [tuple(net.params[f"layer_{i + 1}"]["mha"][w].shape)
              for i in range(5) for w in ("Wq", "Wk", "Wo", "Wog")]
    assert shapes[:4] == [(64, 96), (64, 32), (96, 64), (64, 6)]
    assert shapes[4:8] == [(64, 128), (64, 32), (128, 64), (64, 8)]
    assert "moe" not in net.params["layer_1"]
    assert net.params["layer_1"]["Wg"].shape == (64, 96)
    assert net.params["layer_2"]["moe"]["Wsg"].shape == (64, 24)
    one = CausalTransformerLM(vocab_size=64, hidden=64, n_layers=2,
                              n_heads=4)
    assert di.layer_heads(one, 1) == 4 and di.layer_theta(one, 1) == 1e4
    assert "Wog" not in one.init(seq_len=16).params["layer_1"]["mha"]


@pytest.mark.parametrize("kw,why", [
    ({"heads_by_layer": [6, 8, 8, 8]}, "heads_by_layer"),
    ({"heads_by_layer": [6, 8, 8, 8, 5]}, "heads_by_layer"),
    ({"rope_by_kind": {"full": None}}, "rope_by_kind"),
    ({"rope_layers": [1, 2]}, "rope_by_kind"),
    ({"mixer": "power_retention", "window": None, "window_layers": None,
      "experts": None}, "softmax"),
])
def test_the_kinds_properties_are_checked(kw, why):
    args = dict(
        vocab_size=64, hidden=64, n_layers=5, n_heads=6, n_kv_heads=2,
        head_dim=16, window=WINDOW, window_layers=[1, 2, 3],
        heads_by_layer=HEADS, rope_by_kind=RULES, attn_gate=True,
        experts=EXPERTS)
    args.update(kw)
    with pytest.raises(ValueError, match=why):
        CausalTransformerLM(**args)


# -- the model's forwards ----------------------------------------------------

def _reference_logits(params, seq, t0, rows, faults=()):
    from benchmarks.reference import gated_window_moe_lm as ref
    with jax.default_matmul_precision("highest"):
        logits, margin = ref.logits_from(
            params, jnp.asarray(seq), t0 - 1, d=ref.dims(_TOY),
            rows=rows, faults=faults)
    return np.asarray(logits), np.asarray(margin)


def test_the_training_forward_equals_the_reference(gated_lm):
    """``fit``'s plain forms (the masked einsum, every expert on every
    row, the gate in the layer) give the reference's logits over a
    sequence that crosses the window."""
    model, net = gated_lm
    seq = np.random.default_rng(1).integers(0, 64, 64).astype(np.int32)
    got = np.log(np.asarray(net.output(seq[None], train=False))[0])
    want, _ = _reference_logits(net.params, seq, 1, 64)
    want = want - np.log(np.exp(want).sum(-1, keepdims=True))
    assert np.abs(got - want).max() < 2e-4


def test_fit_trains_the_gate_the_router_and_both_kinds_of_expert():
    """One step at test size through the plain forms: the loss is
    finite and the gate, the router, the shared expert and the routed
    ones move (their gradients are not zero)."""
    model = _model()
    net = model.init(seq_len=64)
    before = jax.tree.map(np.asarray, net.params["layer_2"])
    rng = np.random.default_rng(0)
    x = rng.integers(0, 64, (2, 64)).astype(np.int32)
    net.fit(x, np.roll(x, -1, axis=1))
    assert np.isfinite(float(net.score()))
    after = net.params["layer_2"]
    moved = lambda *path: np.abs(
        np.asarray(_at(after, path)) - _at(before, path)).max()
    for path in (("mha", "Wog"), ("moe", "Wr"), ("moe", "Wsg"),
                 ("moe", "Weg")):
        assert moved(*path) > 0, path


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def test_generate_equals_the_training_forward(gated_lm):
    """Dense ``generate()`` keeps every position and masks by the
    window: its greedy tokens are the training forward's, past the
    window."""
    model, net = gated_lm
    prompt = np.random.default_rng(4).integers(0, 64, (1, 20)).astype(
        np.int32)
    out = model.generate(net, prompt, 44)
    probs = np.asarray(net.output(out[:, :-1], train=False))[0]
    np.testing.assert_array_equal(probs[19:].argmax(-1), out[0, 20:])


# -- the gateway's path against the plain reference ----------------------

#: float32 on the CPU, logits up to 3 in size: the bucket prefill and
#: the decode over pages differ from the reference's one pass in the
#: order of float32 sums only (read: 1.4e-5 at most over the three
#: cases). A gate left out or read from the un-normed rows, a full
#: layer turned over all 16 features or by plain frequencies or
#: without its factor, a window layer under the full layer's rule, a
#: window ignored, a shared expert or the 2.5 left out or misplaced
#: each move a logit by 0.05 to 5; a router rounded to bf16 by 1.7e-4
#: where no choice flips (15 positions short of the window) and by
#: 3e-2 where one does
LOGIT_TOL = 5e-5


@pytest.mark.parametrize("t0,n_new", [(20, 70), (50, 50), (9, 15)],
                         ids=["crosses_the_window",
                              "prompt_longer_than_the_window",
                              "shorter_than_the_window"])
def test_prefill_then_paged_decode_matches_the_reference_logits(
        gated_lm, t0, n_new):
    """A sequence that crosses the window in decode (the ring wraps
    twice), a prompt longer than the window (the bucket prefill keeps
    a window layer's last ring of pages only) and one that never
    reaches it: the pages written at admission and at every decoded
    position are the reference's, by the logits they give."""
    model, net = gated_lm
    rng = np.random.default_rng(t0)
    seq = rng.integers(0, 64, t0 + n_new).astype(np.int32)
    first, got = _served_logits(model, net, seq, t0)
    want, margin = _reference_logits(net.params, seq, t0, n_new + 1)
    clear = margin[1:] > 1e-4
    assert clear.sum() >= n_new - 2
    assert first == int(want[0].argmax())
    assert np.abs(got - want[1:])[clear].max() < LOGIT_TOL
    faults = ["no_gate", "gate_from_x", "full_rotary_all",
              "full_plain_freq", "no_attention_factor",
              "window_full_rule", "no_shared", "no_scale",
              "scale_on_shared", "no_renorm", "sigmoid_scores",
              "drop_route", "bf16_router"]
    if t0 + n_new > WINDOW:
        faults += ["no_window", "window_off_by_one_page"]
    for fault in faults:
        other, _ = _reference_logits(net.params, seq, t0, n_new + 1,
                                     (fault,))
        # (a router rounded to bf16 moves every weight a little even
        # where no choice flips; the others move logits by far more)
        bar = 2 if fault == "bf16_router" else 100
        assert np.abs(got - other[1:]).max() > bar * LOGIT_TOL, fault


def test_bf16_serving_stays_within_bf16_of_the_reference():
    """The same comparison in the compute dtype the cell serves in:
    weights kept as their bf16 rounding (the router in float32), the
    pools in bf16. Away from routing ties the logits lie within a few
    bf16 ulps of a value near 3."""
    model = _model(compute_dtype="bfloat16")
    net = model.init(seq_len=64)
    net.params = model.decode_params(net)
    assert net.params["layer_2"]["moe"]["Wr"].dtype == jnp.float32
    assert net.params["layer_2"]["moe"]["Wsg"].dtype == jnp.bfloat16
    assert net.params["layer_2"]["mha"]["Wog"].dtype == jnp.bfloat16
    seq = np.random.default_rng(3).integers(0, 64, 80).astype(np.int32)
    _, got = _served_logits(model, net, seq, 23)
    want, margin = _reference_logits(net.params, seq, 23, 58)
    clear = margin[1:] > 0.05
    assert clear.sum() >= 10
    assert np.abs(got - want[1:])[clear].max() < 0.15
    assert np.abs(got - want[1:])[clear].max() > LOGIT_TOL


def test_a_sequence_at_full_context_holds_one_ring_of_a_window_layer():
    """The cell's sizes: window 512, block 16, 11,264 positions: a ring
    of 33 pages, whatever the sequence's length."""
    pager = kv_pager.KVPager(
        n_layers=2, n_kv_heads=8, head_dim=128, block=16,
        n_pages=1 + 2 * 11264 // 16, cache_quant=None, dtype="bfloat16",
        windowed=(di.WindowSpec(512, KINDS), 2))
    assert pager.ring == kv_pager.ring_pages(512, 16) == 33
    assert pager.pool[1].shape == (3, 1 + 2 * 33, 16 * 8, 256)
    owner = object()
    assert pager.alloc(pager.pages_for(11264), owner) is not None
    pager.check_invariants()
    ids, src, dst = pager.prompt_pages(1, list(range(1, 705)), 8192, 8000)
    # a bucket prefill writes a window layer's last 33 pages only: the
    # 512 + block positions that end at the prompt's last page
    assert src.shape == dst.shape == (33,)
    assert int(src[-1]) == (8000 - 1) // 16 and int(src[0]) == 499 - 32
    assert sorted(np.asarray(dst)) == list(range(1 + 33, 1 + 66))
    pager.pool = (pager.pool[0], pager.pool[1][:, :-1])
    with pytest.raises(kv_pager.PageTableError, match="window pool"):
        pager.check_invariants()


def test_gateway_serves_the_gated_model_and_wraps_rings_in_decode(
        gated_lm):
    """Through the public gateway, two tenants: the served tokens are
    dense ``generate()``'s, the step records carry the walks' counts
    and the experts' pairs, and every page of both kinds comes back."""
    from deeplearning4j_tpu import obs
    model, net = gated_lm
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
        gw._sched.pager.check_invariants()
    finally:
        gw.shutdown(drain=False, timeout=30)
    for p, got in zip(prompts, served):
        want = model.generate(net, p[None], 60)[0]
        np.testing.assert_array_equal(got, want)
    steps = [r.counts for r in obs.trace.records()
             if r.name == "serving.decode_step" and r.counts
             and "kv_rows_read" in r.counts]
    assert any(c["ring_overwrites"] for c in steps)
    last = [c for c in steps if c.get("expert_pairs")]
    # every live row makes top_k pairs in each of the FOUR sparse
    # layers (the leading dense layer makes none)
    assert all(c["expert_pairs"] % (4 * 2) == 0 for c in last)
    assert all(c["experts_hit"] <= 4 * 16 for c in last)


@pytest.mark.parametrize("option,why", [
    ({"prefix_sharing": True}, "prefix_sharing"),
    ({"spec_k": 2}, "spec_k")])
def test_scheduler_still_refuses_what_a_ring_cannot_serve(gated_lm, option,
                                                          why):
    model, net = gated_lm
    with pytest.raises(ValueError, match=why):
        DecodeScheduler(model, net, max_slots=2, block=BLOCK,
                        max_context=128, **option)

"""The hybrid state-space configuration's pieces: that its file keeps
every published key, its shape functions (the arithmetic ISSUE 43
sized the cell by, to the byte), how its ``correct`` fails (the float8
control; eight faults injected into the PROGRAM), what the cell
reports, and its roofline reader on a made-up observation.

A file of its own: a PR that adds a configuration edits no file the
benchmark already has.
"""
import json

import pytest
from conftest import ROOT, toy_spec

from benchmarks import run
from benchmarks.drivers import serve_open_loop
from benchmarks.readers import trace_ssm
from benchmarks.trace import shapes_ssm as shapes

CELL = "granite4h.chat-saturated"


def config():
    return json.loads((ROOT / "benchmarks" / "configs"
                       / "granite-4.0-h-micro.json").read_text())


def test_the_configuration_keeps_every_published_number():
    cfg = config()
    # (the catalog lies outside the repository: its values, by hand)
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert {k: cfg[k] for k in published} == published
    kinds = cfg["layer_types"]
    assert len(kinds) == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    assert set(kinds) == {"mamba", "attention"}
    assert cfg["reduced"] == [] and "reduced_from" not in cfg
    assert cfg["builder"] == cfg["reference"] == "hybrid_ssm_lm"


def test_granite_weights_to_the_byte():
    cfg = config()
    # in_proj 2048 x 8512, conv 4352 x 4 + 4352, 3 x 64, gated norm
    # 4096, out_proj 4096 x 2048, two norms, the feed-forward
    assert shapes.mamba_layer_params(cfg) == 76_182_976
    assert shapes.attention_layer_params(cfg) == 60_821_504
    assert shapes.embedding_params(cfg) == 205_520_896
    assert shapes.params(cfg) == (36 * 76_182_976 + 4 * 60_821_504
                                  + 205_520_896 + 2048)
    # 3,191.4 M parameters, 6.38 GB in bf16 alone; a decode step reads
    # all of it (the tied head is the embedding matrix)
    assert shapes.weight_bytes(cfg) == 6_382_792_192
    assert shapes.decode_weight_bytes(cfg) == 6_382_792_192


def test_granite_state_and_pages_to_the_byte():
    cfg = config()
    wl = run.resolve(CELL)["workload"]
    gw = wl["driver_params"]["gateway"]
    assert shapes.state_bytes_per_layer(cfg) == 2_097_152
    assert shapes.tail_bytes_per_layer(cfg) == 3 * 4352 * 2
    # 65 pages x 36 layers: H 4.91 GB, tails 0.06 GB
    assert shapes.state_pool_bytes(cfg, gw["max_slots"]) == 4_968_437_760
    assert shapes.kv_bytes_per_row(cfg) == 8_192
    assert shapes.kv_pool_bytes(cfg, gw["max_slots"],
                                gw["max_context"]) == 1_610_612_736
    assert shapes.decode_h_bytes_per_slot(cfg) == 2 * 36 * 2_097_152
    assert shapes.decode_state_bytes_per_slot(cfg) == 2 * 36 * (
        2_097_152 + 26_112)
    # with the slots full the states are 59% of what a step must move
    state = gw["max_slots"] * shapes.decode_state_bytes_per_slot(cfg)
    assert 0.58 < state / (state + shapes.decode_weight_bytes(cfg)) < 0.61
    # the page size the step roofline counts by is the gateway's
    spec = json.loads((ROOT / "benchmarks" / "metrics"
                       / "decode_roofline.ssm.json").read_text())
    assert spec["args"]["block"] == gw["block"]


def test_the_program_counts_the_same_state_bytes():
    """``state_bytes`` on the program's records is the shape
    function's number, so a share of a roofline means what it says."""
    from benchmarks.models import hybrid_ssm_lm
    from deeplearning4j_tpu.serving.kv_pager import KVPager
    cfg = config()
    pager = KVPager(n_layers=4, n_kv_heads=8, head_dim=64, n_pages=2,
                    block=16, cache_quant=None, dtype="bfloat16",
                    ssm=(hybrid_ssm_lm.spec(cfg), 1))
    assert (pager.state_bytes_per_slot
            == shapes.decode_state_bytes_per_slot(cfg))
    assert pager.state_pool_bytes() == shapes.state_pool_bytes(cfg, 1)
    assert [a.shape for a in pager.pool] == [
        (4, 2, 16, 8, 128), (36, 2, 128, 4096), (36, 2, 3 * 4352)]


def test_the_builder_refuses_what_it_does_not_serve():
    from benchmarks.models import hybrid_ssm_lm
    for key, value in (("num_local_experts", 8),
                       ("position_embedding_type", "rope"),
                       ("attention_bias", True),
                       ("mamba_proj_bias", True), ("mamba_n_groups", 8),
                       ("tie_word_embeddings", False)):
        with pytest.raises(ValueError, match="does not serve"):
            hybrid_ssm_lm.spec(dict(config(), **{key: value}))


def context(seed=5, seconds=2.0):
    return run.Context(toy_spec(CELL), seed, seconds)


def test_hybrid_control_in_float8_is_not_correct():
    ctx = context()
    got = serve_open_loop.readings(ctx)
    limit = ctx.config["correct"]["served_logit_gap"]["limit"]
    assert got["program"]["positions"] > 20
    assert got["program"]["served_logit_gap"] <= limit
    assert got["control_fp8"]["served_logit_gap"] > limit


def _tail_not_carried(mp):
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving import kv_pager
    state = kv_pager.SSMChunk.state

    def fresh_tail(self, li):
        h, tail = state(self, li)
        return h, jnp.zeros_like(tail)

    mp.setattr(kv_pager.SSMChunk, "state", fresh_tail)


def _no_dt_bias(mp):
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import ssm
    step_size = ssm.step_size
    mp.setattr(ssm, "step_size", lambda mha, dt: step_size(
        dict(mha, dt_bias=jnp.zeros_like(mha["dt_bias"])), dt))


def _no_softplus(mp):
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import ssm

    def step_size(mha, dt):     # the raw step: mostly negative
        raw = (dt.astype(jnp.float32)
               + mha["dt_bias"].astype(jnp.float32))
        return raw, -jnp.exp(mha["A_log"].astype(jnp.float32))

    mp.setattr(ssm, "step_size", step_size)


def _no_skip(mp):
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import ssm
    rows, chunk = ssm.mixer_rows, ssm.mixer_chunk

    def without(fn):
        return lambda mha, *a, **kw: fn(
            dict(mha, D=jnp.zeros_like(mha["D"])), *a, **kw)

    mp.setattr(ssm, "mixer_rows", without(rows))
    mp.setattr(ssm, "mixer_chunk", without(chunk))


def _gate_after_norm(mp):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import ssm

    def gated_norm(mha, y, z, eps):
        y = y.astype(jnp.float32)
        ms = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
        y = y * jax.lax.rsqrt(ms + eps) * mha["norm_gamma"].astype(
            jnp.float32)
        return (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)

    mp.setattr(ssm, "gated_norm", gated_norm)


def _no_residual_multiplier(mp):
    from deeplearning4j_tpu.nn import decoder_infer as di
    block = di.block
    mp.setattr(di, "block", lambda *a, **kw: block(
        *a, **dict(kw, residual=None)))


def _scores_by_root_d(mp):
    from deeplearning4j_tpu.nn import decoder_infer as di
    mp.setattr(di, "q_fold", lambda dims, d: 1.0)


def _rotary(mp):
    from deeplearning4j_tpu.nn import decoder_infer as di
    rotary_rows = di.rotary_rows
    mp.setattr(di, "rotary_rows",
               lambda x, theta, pos: rotary_rows(x, 10000.0, pos))


def _state_held_in_bf16(mp):
    """The state pool rounded to bf16 wherever a program writes it (the
    arithmetic stays float32): what a bf16 state pool would hold."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.serving import kv_pager
    attend, keep = kv_pager.PagedSSM.attend, kv_pager.SSMChunk.keep

    def rounded(h):
        return h.astype(jnp.bfloat16).astype(jnp.float32)

    def attend_rounded(self, li, mha, h):
        a = attend(self, li, mha, h)
        state, tails = self.pool
        self.pool = (state.at[li].set(rounded(state[li])), tails)
        return a

    mp.setattr(kv_pager.PagedSSM, "attend", attend_rounded)
    mp.setattr(kv_pager.SSMChunk, "keep", lambda self, li, state, tail:
               keep(self, li, rounded(state), tail))


#: Every one fails the comparison at TOY size (chunk 16, a toy score
#: scale). At the cell's size, read on the chip through
#: ``benchmarks/tools/read_faults.py`` (PERF.md section 2): the five of
#: the Mamba layers' and the blocks' arithmetic fail there too; a tail
#: not carried (3 rows in 256), scores by ``d^-1/2`` and rotary applied
#: (4 layers of 40, near-uniform at the published 1/64) pass.
FAULTS = {"conv_tail_not_carried": _tail_not_carried,
          "dt_bias_left_out": _no_dt_bias,
          "softplus_left_out": _no_softplus,
          "skip_left_out": _no_skip,
          "gate_after_the_norm": _gate_after_norm,
          "residual_multiplier_left_out": _no_residual_multiplier,
          "scores_by_root_d": _scores_by_root_d,
          "rotary_applied": _rotary}


#: What the cell's comparison does NOT see (PERF.md section 7): a
#: state held in bf16 moves a logit by 1e-3 of its size, a hundredth of
#: what the bf16 matmuls around it already do, so no served TOKEN moves,
#: at toy size or at the cell's (read on the chip with
#: ``benchmarks/tools/read_faults.py``). The float32 state is held at
#: the logits, by ``tests/test_ssm.py``.
HOLES = {"state_held_in_bf16": _state_held_in_bf16}


def long_answers(tmp_path, seed=2**31 + 11, seconds=3.0):
    """The toy cell with answers of 32 to 96 tokens after prompts of 8
    to 32: the sample then holds some 400 served positions over up to
    four chunks and 100 decode steps each, enough for a fault of a
    percent in one layer's state to move a served token."""
    from conftest import REHEARSAL_DEVICE
    spec = toy_spec(CELL)
    params = spec["workload"]["traffic"]["params"]
    params["prompt"].update(median=20, min=8, max=32)
    params["output"].update(median=64, min=32, max=96)
    return run.run_cell(spec, seed, seconds, False, REHEARSAL_DEVICE,
                        tmp_path / "trace")


def test_the_sound_program_is_correct_at_toy_size(tmp_path):
    assert long_answers(tmp_path)["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_hybrid_program_is_not_correct(tmp_path, monkeypatch,
                                                fault):
    FAULTS[fault](monkeypatch)
    assert long_answers(tmp_path)["correct"] is False


@pytest.mark.parametrize("hole", sorted(HOLES))
def test_a_hole_of_the_comparison_is_known_by_name(tmp_path, monkeypatch,
                                                    hole):
    """A fault the served tokens do not show. When a later comparison
    sees it, move it to ``FAULTS`` and strike it from PERF.md."""
    HOLES[hole](monkeypatch)
    assert long_answers(tmp_path)["correct"] is True


def test_the_cell_reports_the_saturated_cell_s_quantities_by_their_names(
        toy_cell, monkeypatch):
    # one quantity, one name: what the existing readers read of this
    # cell goes under the accepted .saturated metrics; only the three
    # whose numerators are the configuration's own carry its suffix
    spec = run.resolve(CELL)
    names = {m["name"] for m in spec["per_layer"]}
    assert {n for n in names if n.endswith(".ssm")} == {
        "state_roofline.ssm", "decode_roofline.ssm", "attn_page_ms.ssm"}
    assert "idle_named_share.saturated" not in names
    assert [m["name"] for m in spec["end_to_end"]] == [
        "serve_tokens_per_s", "setup_s"]
    # the toy's step takes a fraction of a millisecond on the CPU: a
    # saturated window and the profiler's stop make more records than
    # the default ring holds (the chip's 25 ms step makes 40 a second)
    from deeplearning4j_tpu import obs
    monkeypatch.setenv("DL4J_TPU_TRACE_RING", str(1 << 20))
    obs.trace.reset()
    try:
        result = toy_cell(CELL, seconds=3.0, trace=True)
    finally:
        monkeypatch.undo()
        obs.trace.reset()
    # (persistent_hit_share needs a cache: a CPU process keeps none)
    assert {"compile_s", "sched_step_ms.saturated",
            "slot_occupancy.saturated", "prefill_pad_share.saturated",
            "sched_host_gap_ms.saturated"} <= set(result["metrics"])
    assert 0 < result["metrics"]["slot_occupancy.saturated"]["value"] <= 100


class _Rec:
    def __init__(self, name, t, counts):
        self.name, self.stamps, self.counts = name, (t, t + 0.01), counts


def _observation(cfg):
    return {"window": [100.0, 130.0], "trace_window_s": 3.0,
            "config": cfg, "device": {"kind": "TPU v5 lite"},
            "trace": {"devices": [{
                "ops": [["fusion.3", 0, 900_000]],
                "modules": [["jit_step(1)", 0, 4_000_000],
                            ["jit_step(1)", 0, 6_000_000],
                            ["jit_admit(2)", 0, 9_000_000]]}]}}


def test_hybrid_roofline_reader(monkeypatch):
    cfg = toy_spec(CELL)["config"]
    obs = _observation(cfg)
    per_slot = shapes.decode_state_bytes_per_slot(cfg)

    def step(at, active, pages, said=None):
        return _Rec("serving.decode_step", at, {
            "active": active, "kv_pages": pages,
            "state_bytes": active * per_slot if said is None else said})

    # the record outside the tail is not looked at, count and all
    records = [step(128.0, 2, 10), step(129.0, 4, 30),
               step(110.0, 9, 5, said=1),
               _Rec("serving.prefill", 128.5, {"chunks": 2})]
    monkeypatch.setattr(trace_ssm.timeline, "window_records",
                        lambda obs: records)
    # the step's share: 3 slots' states and tails, the weights, 20
    # pages of 16 rows, over the mean 5 ms step
    args = {"kind": "step", "module": "^jit_step", "block": 16}
    need = (3 * per_slot + shapes.decode_weight_bytes(cfg)
            + 20 * 16 * shapes.kv_bytes_per_row(cfg))
    assert trace_ssm.read(obs, args) == pytest.approx(
        100 * need / (5e-3 * 819e9))
    # the recurrence's share: by SCOPE, through the scope reader
    seen = {}

    def scope_ms(obs, a):
        seen.update(a)
        return 0.25

    monkeypatch.setattr(trace_ssm.trace_scope, "read", scope_ms)
    args = {"kind": "state", "module": "^jit_step",
            "scope": "(^|/)ops\\.ssm_decode(/|$)"}
    assert trace_ssm.read(obs, args) == pytest.approx(
        100 * 3 * shapes.decode_h_bytes_per_slot(cfg)
        / (0.25e-3 * 819e9))
    assert seen == {"kind": "ms", "per": "program",
                    "module": "^jit_step", "scope": args["scope"]}
    monkeypatch.setattr(trace_ssm.trace_scope, "read",
                        lambda obs, a: None)
    assert trace_ssm.read(obs, args) is None
    # the program's own count has to agree with the shapes
    monkeypatch.setattr(
        trace_ssm.timeline, "window_records",
        lambda obs: records + [step(129.5, 4, 7, said=4 * per_slot + 8)])
    with pytest.raises(ValueError, match="state bytes"):
        trace_ssm.read(obs, args)
    # a parent commit, or another family's cell: no ring, records
    # without both counts, a configuration without layer kinds
    monkeypatch.setattr(trace_ssm.timeline, "window_records",
                        lambda obs: None)
    assert trace_ssm.read(obs, args) is None
    monkeypatch.setattr(
        trace_ssm.timeline, "window_records",
        lambda obs: [_Rec("serving.decode_step", 128.0,
                          {"active": 3, "kv_pages": 9, "state_bytes": 0})])
    assert trace_ssm.read(obs, args) is None
    assert trace_ssm.read({"trace": None}, args) is None
    assert trace_ssm.read(dict(obs, config={"hidden_size": 8}),
                          args) is None


def test_the_scope_pattern_reads_the_recurrence_whatever_runs_it():
    """The metric files' patterns against scope paths as
    ``trace_scope.label`` writes them."""
    import re
    state = json.loads((ROOT / "benchmarks" / "metrics"
                        / "state_roofline.ssm.json").read_text())["args"]
    pages = json.loads((ROOT / "benchmarks" / "metrics"
                        / "attn_page_ms.ssm.json").read_text())["args"]
    mamba = "paged_decode.block_3.mixer/ops.ssm_decode"
    attn = "paged_decode.block_5.mixer/ops.paged_decode_attention"
    assert re.search(state["scope"], mamba)
    assert re.search(state["scope"], mamba + "/ops.rms_norm")
    assert not re.search(state["scope"],
                         "paged_decode.block_3.mixer/ops.ssm_conv")
    assert not re.search(state["scope"], attn)
    assert re.search(pages["scope"], attn)
    assert not re.search(pages["scope"], mamba)

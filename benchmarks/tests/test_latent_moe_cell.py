"""The latent-attention, mixture-of-experts configuration's pieces:
its shape functions (the arithmetic ISSUE 38 sized the cell by), how
its ``correct`` fails (the float8 control; a dropped route, a bf16
router and a latent stored without its norm in the PROGRAM), what the
cell reports, and its reader on a made-up observation.

A file of its own: a PR that adds a configuration edits no file the
benchmark already has.
"""
import json

import pytest
from conftest import ROOT, toy_spec

from benchmarks import run
from benchmarks.drivers import serve_open_loop
from benchmarks.readers import trace_moe
from benchmarks.trace import shapes_latent_moe as shapes

CELL = "deepseekv3.decode-saturated"


def config():
    return json.loads((ROOT / "benchmarks" / "configs"
                       / "deepseek-v3-5l-ep16.json").read_text())


def test_the_configuration_keeps_every_published_number():
    cfg = config()
    # (the catalog lies outside the repository: its values, by hand)
    published = {
        "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "n_group": 8, "topk_group": 4, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "rope_theta": 10000}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers",
                              "first_k_dense_replace",
                              "n_routed_experts", "vocab_size"]
    assert cfg["reduced_from"] == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 129280}
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == 129280


def test_deepseek_weights_by_the_issue_s_arithmetic():
    cfg = config()
    # q_a 11.0 + q_b 37.7 + kv_a 4.1 + kv_b 16.8 + o 117.4 M
    assert shapes.attention_params(cfg) == 187_105_280
    assert shapes.dense_ffn_params(cfg) == 396_361_728
    assert shapes.expert_params(cfg) == 44_040_192
    assert shapes.router_params(cfg) == 1_835_008
    # one of 16 chips: attention + shared + router + 16 experts
    assert shapes.expert_layer_params(cfg) == 937_623_552
    assert shapes.embedding_and_head_params(cfg) == 231_669_760
    # one dense + four expert layers + an eighth of the vocabulary
    assert shapes.weight_params(cfg) == 4_565_630_976
    # bf16, the four routers in float32: 9.15 GB
    assert shapes.weight_bytes(cfg) == 2 * 4_565_630_976 + 2 * 4 * 1_835_008


def test_deepseek_cache_and_step_bytes():
    cfg = config()
    assert shapes.latent_row_values(cfg) == 576
    # 5 layers x 1,152 B a position
    assert shapes.latent_row_bytes(cfg) == 5_760
    # 64 slots x 6,144 positions: 2.26 GB
    assert shapes.latent_pool_bytes(cfg, 64, 6144) == 2_264_924_160
    # the absorbed attention does 242 operations a byte of cache: the
    # v5e's ridge (197 TFLOP/s over 819 GB/s) is 240
    assert round(shapes.latent_row_flops(cfg)
                 / shapes.latent_row_bytes(cfg)) == 242
    # every step: 5 attentions, the dense feed-forward, 4 shared
    # experts, 4 float32 routers, the head
    fixed = shapes.decode_fixed_weight_bytes(cfg)
    assert fixed == 2 * (5 * 187_105_280 + 396_361_728
                         + 4 * 44_040_192 + 16160 * 7168) \
        + 4 * 4 * 1_835_008
    # with every held expert hit the experts are 5.6 of 8.9 GB
    hit = 4 * 16 * shapes.expert_bytes(cfg)
    assert 0.62 < hit / (hit + fixed) < 0.64


def context(seed=5, seconds=2.0):
    return run.Context(toy_spec(CELL), seed, seconds)


def test_latent_moe_control_in_float8_is_not_correct():
    ctx = context()
    got = serve_open_loop.readings(ctx)
    limit = ctx.config["correct"]["served_logit_gap"]["limit"]
    assert got["program"]["positions"] > 20
    assert got["program"]["served_logit_gap"] <= limit
    assert got["control_fp8"]["served_logit_gap"] > limit


def _drop_a_route(monkeypatch):
    from deeplearning4j_tpu.ops import moe
    sound = moe.route

    def route(*a, **kw):
        ids, w = sound(*a, **kw)
        return ids, w.at[:, 0].set(0.0)
    monkeypatch.setattr(moe, "route", route)


def _bf16_router(monkeypatch):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import moe
    sound = moe.route

    def route(h, w_r, bias, **kw):
        coarse = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
        # scores rounded as a bf16 router would leave them
        with jax.default_matmul_precision("bfloat16"):
            return sound(coarse(h), coarse(w_r), bias, **kw)
    monkeypatch.setattr(moe, "route", route)


def _latent_without_its_norm(monkeypatch):
    from deeplearning4j_tpu.ops import latent
    monkeypatch.setattr(latent, "_rms", lambda x, gamma: x)


@pytest.mark.parametrize("fault", [
    _drop_a_route, _bf16_router, _latent_without_its_norm])
def test_a_faulty_program_is_not_correct(toy_cell, monkeypatch, fault):
    assert toy_cell(CELL, seconds=2.0)["correct"] is True
    fault(monkeypatch)
    assert toy_cell(CELL, seconds=2.0)["correct"] is False


def test_the_cell_reports_the_saturated_cell_s_quantities_by_their_names(
        toy_cell):
    spec = run.resolve(CELL)
    names = {m["name"] for m in spec["per_layer"]}
    own = {n for n in names if n.endswith((".moe", ".mla"))}
    assert own == {"decode_roofline.moe", "expert_roofline.moe",
                   "latent_roofline.mla",
                   "expert_load_max_over_mean.moe"}
    assert not {n for n in names if n.startswith("idle_named_share")}
    result = toy_cell(CELL, seconds=3.0, trace=True)
    assert {"compile_s", "sched_step_ms.saturated",
            "slot_occupancy.saturated",
            "sched_host_gap_ms.saturated"} <= set(result["metrics"])
    assert 0 < result["metrics"]["slot_occupancy.saturated"]["value"] <= 100


class _Rec:
    def __init__(self, name, t, counts):
        self.name, self.stamps, self.counts = name, (t, t + 0.01), counts


def test_latent_moe_reader(monkeypatch):
    cfg = config()
    obs = {"window": [100.0, 130.0], "trace_window_s": 3.0,
           "config": cfg, "device": {"kind": "TPU v5 lite"},
           "max_slots": 64,
           "trace": {"devices": [{
               "ops": [["fusion.3", 0, 900_000]],
               "modules": [["jit_step(1)", 0, 16_000_000],
                           ["jit_step(1)", 0, 16_000_000],
                           ["jit_admit(2)", 0, 90_000_000]]}]}}

    def step(at, hit, rows, pairs=120, top=16, ahead=1, active=60):
        return _Rec("serving.decode_step", at, {
            "active": active, "ahead": ahead, "latent_rows": rows,
            "expert_pairs": pairs, "experts_hit": hit,
            "expert_pairs_max": top})

    # means over the tail's records that read a step: 56 experts hit,
    # 70,000 rows; the record outside the tail and the one that read
    # nothing are not looked at
    records = [step(128.0, 52, 60_000), step(129.0, 60, 80_000),
               step(110.0, 1, 1), step(129.5, 0, 9, pairs=0, top=0,
                                       ahead=0),
               _Rec("serving.prefill", 128.5, {"expert_pairs": 9})]
    monkeypatch.setattr(trace_moe.timeline, "window_records",
                        lambda obs: records)
    need = (shapes.decode_fixed_weight_bytes(cfg)
            + 56 * shapes.expert_bytes(cfg) + 70_000 * 5_760)
    got = trace_moe.read(obs, {"kind": "step", "module": "^jit_step"})
    assert got == pytest.approx(100 * need / (16e-3 * 819e9))
    assert got < 100
    # the fullest expert's 16 pairs over a held expert's mean 120 / 16
    assert trace_moe.read(obs, {"kind": "load"}) == pytest.approx(
        16 / 7.5)
    # the experts' loops and the attention's kernel, by the ops' names,
    # inside the two steps only: 12 ms and 1 ms a step
    ops = obs["trace"]["devices"][0]["ops"]
    mods = obs["trace"]["devices"][0]["modules"]
    mods[:] = [["jit_step(1)", 0, 16_000_000],
               ["jit_step(1)", 20_000_000, 16_000_000],
               ["jit_admit(2)", 40_000_000, 90_000_000]]
    for t0 in (0, 20_000_000):
        ops += [[f"while.{i}", t0 + 3_000_000 * i + 100, 3_000_000]
                for i in range(4)]
        ops += [[f"latent_decode_attention.{i}", t0 + 12_500_000
                 + 200_000 * i, 200_000] for i in range(5)]
    ops += [["while.9", 50_000_000, 18_000_000]]    # a prefill's
    args = {"module": "^jit_step"}
    assert trace_moe.read(obs, dict(args, kind="experts", op="^while$")
                          ) == pytest.approx(
        100 * 56 * shapes.expert_bytes(cfg) / 819e9 / 12e-3)
    # 242 operations a byte: the MXU binds, by a hair
    assert trace_moe.read(obs, dict(
        args, kind="latent", op="^latent_decode_attention$")
    ) == pytest.approx(100 * 70_000 * shapes.latent_row_flops(cfg)
                       / 197e12 / 1e-3)
    # no such op in the trace (a parent commit, the CPU)
    assert trace_moe.read(obs, dict(args, kind="experts",
                                    op="^no_such_op$")) is None
    # the program's counts have to fit the configuration
    monkeypatch.setattr(trace_moe.timeline, "window_records",
                        lambda obs: records + [step(129.7, 65, 9)])
    with pytest.raises(ValueError, match="do not fit"):
        trace_moe.read(obs, {"kind": "load"})
    # a parent commit: no ring, or records without the counts
    monkeypatch.setattr(trace_moe.timeline, "window_records",
                        lambda obs: None)
    assert trace_moe.read(obs, {"kind": "load"}) is None
    monkeypatch.setattr(
        trace_moe.timeline, "window_records",
        lambda obs: [_Rec("serving.decode_step", 128.0,
                          {"active": 3, "ahead": 1})])
    assert trace_moe.read(obs, {"kind": "step",
                                "module": "^jit_step"}) is None
    assert trace_moe.read({"trace": None}, {"kind": "load"}) is None

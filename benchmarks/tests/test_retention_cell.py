"""The retention configuration's pieces: its shape functions (the
arithmetic ISSUE 26 sized the cell by, to the byte), how its
``correct`` fails (the float8 control, a step that leaves the gate
out), and its roofline reader on a made-up observation.

These are the cases the issue lists for ``test_correct.py`` and
``test_shapes.py``; they live in a file of their own because a PR
that adds a configuration edits no file the benchmark already has.
"""
import json

import pytest
from conftest import ROOT, toy_spec

from benchmarks import run
from benchmarks.drivers import serve_open_loop
from benchmarks.readers import trace_retention
from benchmarks.trace import shapes_retention as shapes

CELL = "brumby14b.decode-saturated"


def config():
    return json.loads((ROOT / "benchmarks" / "configs"
                       / "brumby-14b-base-6l.json").read_text())


def test_brumby_weights_to_the_byte():
    cfg = config()
    # 5120 * 5120 * 2 + 5120 * 1024 * 2 + 3 * 5120 * 17408
    assert shapes.layer_params(cfg) == 330_301_440
    assert shapes.embedding_and_head_params(cfg) == 1_555_824_640
    # bf16 alone: embedding and head 3.11 GB, a layer 0.66 GB, six
    # layers and both 7.07 GB
    assert 2 * shapes.embedding_and_head_params(cfg) == 3_111_649_280
    assert shapes.weight_bytes(cfg) == 7_075_266_560
    # a decode step reads the layers and the head, not the embedding
    assert shapes.decode_weight_bytes(cfg) == 5_519_441_920


def test_brumby_state_to_the_byte():
    cfg = config()
    assert shapes.state_dim(cfg) == 8256 == cfg["assumed"]["state_dim"]
    # 8 KV heads x 8256 x 128 float32: 33.8 MB a sequence and layer
    assert shapes.state_bytes_per_layer(cfg) == 33_816_576
    # 24 slots x 6 layers: 4.87 GB
    assert shapes.state_pool_bytes(cfg, 24) == 4_869_586_944
    assert shapes.normaliser_bytes_per_layer(cfg) == 264_192
    # a live slot's step: S and z of six layers, read and written
    assert shapes.decode_state_bytes_per_slot(cfg) == 2 * 6 * (
        33_816_576 + 264_192)
    # with the slots full the state is 64% of what a step must move
    state = 24 * shapes.decode_state_bytes_per_slot(cfg)
    share = state / (state + shapes.decode_weight_bytes(cfg))
    assert 0.63 < share < 0.65


def test_the_program_counts_the_same_state_bytes():
    """``state_bytes`` on the program's records is the shape
    function's number, so a share of a roofline means what it says."""
    from deeplearning4j_tpu.ops import retention
    cfg = config()
    d = cfg["head_dim"]
    assert retention.logical_state_rows(d) == shapes.state_dim(cfg)
    per_slot = (2 * 4 * cfg["num_hidden_layers"]
                * cfg["num_key_value_heads"]
                * retention.logical_state_rows(d) * (d + 1))
    assert per_slot == shapes.decode_state_bytes_per_slot(cfg)


def context(seed=5, seconds=2.0):
    return run.Context(toy_spec(CELL), seed, seconds)


def test_retention_control_in_float8_is_not_correct():
    ctx = context()
    got = serve_open_loop.readings(ctx)
    limit = ctx.config["correct"]["served_logit_gap"]["limit"]
    assert got["program"]["positions"] > 20
    assert got["program"]["served_logit_gap"] <= limit
    assert got["control_fp8"]["served_logit_gap"] > limit


def test_a_retention_step_without_its_gate_is_not_correct(
        toy_cell, monkeypatch):
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import retention
    assert toy_cell(CELL, seconds=2.0)["correct"] is True
    monkeypatch.setattr(
        retention, "log_gate",
        lambda gamma: jnp.zeros(gamma.shape, jnp.float32))
    assert toy_cell(CELL, seconds=2.0)["correct"] is False


def test_the_cell_reports_the_saturated_cell_s_quantities_by_their_names(
        toy_cell):
    # one quantity, one name: what the existing readers read of this
    # cell goes under the accepted .saturated metrics; only the two
    # rooflines, whose reader is new, are the configuration's own
    spec = run.resolve(CELL)
    names = {m["name"] for m in spec["per_layer"]}
    own = {n for n in names if n.endswith(".retention")}
    assert own == {"state_roofline.retention", "decode_roofline.retention"}
    result = toy_cell(CELL, seconds=3.0, trace=True)
    # (persistent_hit_share needs a cache: a CPU process keeps none)
    assert {"compile_s", "sched_step_ms.saturated",
            "slot_occupancy.saturated",
            "sched_host_gap_ms.saturated"} <= set(result["metrics"])
    assert 0 < result["metrics"]["slot_occupancy.saturated"]["value"] <= 100


class _Rec:
    def __init__(self, name, t, counts):
        self.name, self.stamps, self.counts = name, (t, t + 0.01), counts


@pytest.mark.parametrize("kind,want", [
    # 3 live slots' state over 6 x 0.5 ms of kernel
    ("state", lambda moved, cfg: 100 * moved / (3e-3 * 819e9)),
    # (the same + the toy's weights) over a 4 ms step
    ("step", lambda moved, cfg: 100 * (
        moved + shapes.decode_weight_bytes(cfg)) / (4e-3 * 819e9))])
def test_retention_roofline_reader(monkeypatch, kind, want):
    cfg = toy_spec(CELL)["config"]
    ops = [[f"retention_decode.{i % 6}", 1000 * i, 500_000]
           for i in range(12)] + [["fusion.3", 0, 900_000]]
    obs = {"window": [100.0, 130.0], "trace_window_s": 3.0,
           "config": cfg, "device": {"kind": "TPU v5 lite"},
           "trace": {"devices": [{
               "ops": ops,
               "modules": [["jit_step(1)", 0, 4_000_000],
                           ["jit_step(1)", 0, 4_000_000],
                           ["jit_admit(2)", 0, 9_000_000]]}]}}
    per_slot = shapes.decode_state_bytes_per_slot(cfg)

    def step(at, active, said=None):
        return _Rec("serving.decode_step", at, {
            "active": active,
            "state_bytes": active * per_slot if said is None else said})

    # bytes are the shapes' own: live slots times a slot's bytes; the
    # record outside the tail is not looked at, count and all
    records = [step(128.0, 2), step(129.0, 4), step(110.0, 9, said=1),
               _Rec("serving.prefill", 128.5, {"chunks": 2})]
    monkeypatch.setattr(trace_retention.timeline, "window_records",
                        lambda obs: records)
    args = {"kind": kind, "op": "retention_decode", "module": "^jit_step"}
    got = trace_retention.read(obs, args)
    assert got == pytest.approx(want(3 * per_slot, cfg))
    # the program's own count has to agree with the shapes
    monkeypatch.setattr(
        trace_retention.timeline, "window_records",
        lambda obs: records + [step(129.5, 4, said=4 * per_slot + 8)])
    with pytest.raises(ValueError, match="state bytes"):
        trace_retention.read(obs, args)
    # a parent commit: no ring, or records without the count
    monkeypatch.setattr(trace_retention.timeline, "window_records",
                        lambda obs: None)
    assert trace_retention.read(obs, args) is None
    monkeypatch.setattr(
        trace_retention.timeline, "window_records",
        lambda obs: [_Rec("serving.decode_step", 128.0, {"active": 3})])
    assert trace_retention.read(obs, args) is None
    assert trace_retention.read({"trace": None}, args) is None

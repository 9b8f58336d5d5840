"""The readers ISSUE 41 adds, on the CPU at toy size: device time by
scope summed from hand-made joined events beside a made-up reduced
trace and ring (the join itself is the program's, and
``tests/test_devtime.py`` holds it to a TPU-form trace), the ratio of
the program's counts, the new metrics' files, and the two scopes of a
decoder block against the block without them. Nothing here is a device
number.
"""
import contextlib
import json
import re
import types

import jax
import pytest
from conftest import ROOT, TOY

from benchmarks import run
from benchmarks.readers import program_counts, trace_scope
from benchmarks.trace import timeline

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PREFILL = {"top": "serving.loop/iter", "step": "serving.prefill",
           "module": "^jit_admit"}
MS = 1_000_000
#: what one chunk program spends where, ns: 8 ms in all
CHUNK = {("prefill.block_0", "prefill.block_0.mixer",
          "ops.retention_prefill"): 5 * MS,
         ("prefill.block_0", "prefill.block_0.ffn"): 2 * MS,
         (): 1 * MS}
#: launches of the tail, ms after the profile's start: a retention
#: admission of three chunks, then one of a single chunk
ADMITS = [[100.0, 110.0, 120.0], [200.0]]
STEPS = [150.0, 160.0, 170.0]


@pytest.fixture
def ring():
    from deeplearning4j_tpu.obs import trace
    trace.reset()
    yield trace
    trace.reset()


def events():
    """What ``devtime.joined_events`` would give for the tail."""
    out = []
    for start in [s for group in ADMITS for s in group]:
        for path, ns in CHUNK.items():
            out.append({"plane": "/device:TPU:0", "module": "jit_admit",
                        "program_id": 111, "launch_ns": start * MS,
                        "op": "copy-done.4", "path": path,
                        "self_ns": float(ns), "backward": False})
    for start in STEPS:
        for path, ns in {("paged_decode.block_0",
                          "paged_decode.block_0.mixer",
                          "ops.retention_decode"): 3 * MS,
                         ("paged_decode.block_0",
                          "paged_decode.block_0.ffn"): MS,
                         ("paged_decode.lm_head",): MS}.items():
            out.append({"plane": "/device:TPU:0", "module": "jit_step",
                        "program_id": 333, "launch_ns": start * MS,
                        "op": "fusion.1", "path": path,
                        "self_ns": float(ns), "backward": False})
    # an op of the step's program that no "XLA Modules" event holds
    out.append({"plane": "/device:TPU:0", "module": "jit_step",
                "program_id": 333, "launch_ns": None, "op": "copy.9",
                "path": (), "self_ns": float(MS), "backward": False})
    return out


def observation(trace, t0: float, shift_ms: float = 0.0) -> dict:
    """The reduced trace of that tail, and ring records around its
    admissions: each record dispatches 0.2 ms before its first program
    starts and reads its token 0.3 ms after its last one ends."""
    def at(ms):
        return t0 + (ms + shift_ms) / 1e3

    modules = [["jit_admit(111)", s * MS, 8 * MS]
               for group in ADMITS for s in group]
    modules += [["jit_step(333)", s * MS, 5 * MS] for s in STEPS]
    for group in ADMITS:
        first, last = group[0], group[-1] + 8.0
        trace.record_phases(
            "serving.prefill",
            (at(first - 1.0), at(first - 0.2), at(last - 7.0),
             at(last + 0.3)), ("h2d", "dispatch", "sync"), 1,
            {"bucket": 64, "t0": 40 * len(group), "chunks": len(group)})
        trace.record("serving.loop/iter", at(first - 1.5),
                     at(last + 0.5), 1)
    return {"trace": {"devices": [{
                "name": "/device:TPU:0", "modules": sorted(
                    modules, key=lambda m: m[1]),
                "ops": [[m[0], m[1], m[2]] for m in modules]}],
                "host": []},
            "window": [t0, t0 + 0.45], "trace_window_s": 0.4}


@pytest.fixture
def joined(ring, monkeypatch):
    """An observation whose join is the hand-made one."""
    t0 = ring.now() - 10.0
    monkeypatch.setattr(timeline, "profile_origin_ns",
                        lambda lo, hi: ring.to_epoch_ns(t0))
    monkeypatch.setattr(trace_scope, "find_xplane", lambda obs: "made-up")
    monkeypatch.setattr(trace_scope, "program", lambda: types.SimpleNamespace(
        joined_events=lambda paths: events()))
    return lambda shift_ms=0.0: observation(ring, t0, shift_ms)


def spec_of(name: str) -> dict:
    return run.load_json(ROOT / "benchmarks" / "metrics" / f"{name}.json")


@pytest.mark.parametrize("metric, want", [
    # (3 + 1) chunks of 8 ms over TWO admissions, not four programs
    ("prefill_device_ms.saturated", 16.0),
    ("prefill_mixer_ms.saturated", 10.0),
    ("prefill_ffn_ms.saturated", 4.0),
    ("step_mixer_ms.saturated", 3.0),
    ("step_ffn_ms.saturated", 1.0),
    # 16 of 16 + 1 ms of the three steps and the loose op; all four
    # admission programs but their copies' millisecond each
    ("scope_joined_share.saturated", 100.0 * (15 + 28) / (16 + 32)),
])
def test_scope_metrics_sum_the_joined_events(joined, metric, want, capsys):
    obs = joined()
    assert trace_scope.read(obs, spec_of(metric)["args"]) == \
        pytest.approx(want)
    out = capsys.readouterr().out
    # the whole table is logged, digits as *, and its rows sum
    assert re.search(r"device time by scope: jit_admit +"
                     r"prefill.block_\*.mixer/ops.retention_prefill +"
                     r"0.020000 s +62.5% +5.0000 ms a launch", out)
    assert re.search(r"jit_admit +all \(self time\) +0.032000 s in 4 "
                     r"launches of 1 programs, 87.50% of it", out)
    # a second metric reads the same join: nothing is joined twice
    trace_scope.read(obs, spec_of(metric)["args"])
    assert "scope join:" not in capsys.readouterr().out


def test_a_training_programs_time_is_per_step(joined):
    obs = dict(joined(), steps_per_program=4)
    args = {"kind": "ms", "module": "^jit_step", "scope": "",
            "per": "program", "steps": "steps_per_program"}
    assert trace_scope.read(obs, args) == pytest.approx(5.0 / 4)


def test_nothing_is_read_without_the_join_or_a_sound_clock(
        joined, monkeypatch, capsys):
    args = spec_of("prefill_device_ms.saturated")["args"]
    # records that lie 50 ms off their programs: the check fails, and
    # what needs the records is not reported; what does not, is
    obs = joined(shift_ms=50.0)
    assert trace_scope.read(obs, args) is None
    assert "clock check FAILED" in capsys.readouterr().out
    assert trace_scope.read(
        obs, spec_of("step_ffn_ms.saturated")["args"]) == 1.0
    # a program without the join (a parent commit), or no device trace
    monkeypatch.setattr(trace_scope, "program", lambda: None)
    assert trace_scope.read(joined(), args) is None
    monkeypatch.undo()
    assert trace_scope.read({"trace": {"devices": [], "host": []},
                             "window": [0.0, 1.0]}, args) is None
    # a program no launch of the tail matches
    assert trace_scope.read(joined(), dict(args, module="^jit_loop")) \
        is None


def test_pad_share_from_three_prefill_records(ring):
    t0 = ring.now()
    for i, counts in enumerate([
            {"bucket": 512, "t0": 300, "chunks": 1},
            {"bucket": 1024, "t0": 700, "chunks": 1},
            {"bucket": 1024, "t0": 1500, "chunks": 2}]):
        a = t0 + 0.1 * (i + 1)
        ring.record_phases("serving.prefill", (a, a + 0.01, a + 0.02,
                                               a + 0.03),
                           ("h2d", "dispatch", "sync"), 1, counts)
    # one before the window and one without the counts do not count
    ring.record_phases("serving.prefill", (t0 - 1.0, t0 - 0.9, t0 - 0.8,
                                           t0 - 0.7),
                       ("h2d", "dispatch", "sync"), 1,
                       {"bucket": 4096, "t0": 1, "chunks": 1})
    ring.record_phases("serving.prefill", (t0 + 0.5, t0 + 0.51, t0 + 0.52,
                                           t0 + 0.53),
                       ("h2d", "dispatch", "sync"), 1, {"bucket": 4096})
    args = spec_of("prefill_pad_share.saturated")["args"]
    obs = {"window": [t0, t0 + 1.0]}
    assert program_counts.read(obs, args) == pytest.approx(
        100.0 * (1 - 2500 / (512 + 1024 + 2048)))
    assert program_counts.read({"window": [t0 + 5.0, t0 + 6.0]},
                               args) is None


NEW = [m for m in BENCH["per_layer"]
       if re.match(r"(scope_joined_share|prefill_(device|mixer|ffn)_ms|"
                   r"prefill_pad_share|step_(mixer|ffn|conv|norm)_ms)\.",
                   m["name"])]


def test_the_new_metrics_are_listed_by_cell_and_have_their_files():
    assert len(NEW) == 16
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in NEW:
        assert set(m["workloads"]) <= cells and m["workloads"]
        spec = spec_of(m["name"])
        assert spec["name"] == m["name"]
        reader = run.Context.plugin("readers", spec["reader"])
        # every cell on the list reports the end-to-end metric it moves
        moved = next(e for e in BENCH["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved["workloads"])
        # without a trace and without the ring there is nothing to read
        assert reader.read({"window": [0.0, 1.0],
                            "trace": {"devices": [], "host": []}},
                           spec["args"]) is None or \
            spec["reader"] == "program_counts"


# -- the block's two scopes are metadata alone ------------------------------

def served(config_name: str):
    """The toy configuration of that name behind a scheduler, and the
    arguments its decode step and one admission program lower from."""
    from deeplearning4j_tpu.serving import DecodeScheduler
    cfg = run.load_json(TOY / "configs" / f"{config_name}.json")
    built = run.Context.plugin("models", cfg["builder"]).build(cfg, 7)
    sched = DecodeScheduler(built["model"], built["net"], max_slots=2,
                            block=16, max_context=64)
    params = built["model"].decode_params(built["net"])
    return sched, params


def lowered_texts(config_name: str):
    import jax.numpy as jnp
    sched, params = served(config_name)
    sds, i32 = jax.ShapeDtypeStruct, jnp.int32
    scalars = (sds((), i32), sds((), jnp.float32), sds((), jnp.float32),
               sds((), i32))
    step = sched._step_fn.lower(params, sched.pager.pool,
                                *sched._step_feed_shapes())
    if sched.recurrent:
        admit = sched._chunk_fn.lower(
            params, sched.pager.pool, sched._prefill_hist, sds((), i32),
            sds((1, sched.prefill_chunk), i32), sds((), i32), *scalars)
    else:
        admit = sched._admit_fn(32).lower(
            params, sched.pager.pool, sds((32 // sched.block,), i32),
            sds((1, 32), i32), *scalars)
    return [(low.as_text(), low.as_text(debug_info=True))
            for low in (step, admit)]


@pytest.mark.parametrize("config_name", [
    "mistral-7b-v0.3-6l", "brumby-14b-base-6l", "deepseek-v3-5l-ep16"])
def test_the_blocks_two_scopes_change_no_op(config_name, monkeypatch):
    """Softmax, retention and latent mixers: the decode step and an
    admission program lower to the same operations in the same order
    with and without ``.mixer`` / ``.ffn`` (the parent's block): the
    texts are equal once locations, where a scope lives, are left out."""
    from deeplearning4j_tpu.nn import decoder_infer
    from deeplearning4j_tpu.obs import devtime
    with_scopes = lowered_texts(config_name)

    def parents(name):
        return (contextlib.nullcontext()
                if name.endswith((".mixer", ".ffn"))
                else devtime.scope(name))

    monkeypatch.setattr(decoder_infer, "devtime",
                        types.SimpleNamespace(scope=parents))
    without = lowered_texts(config_name)
    for (ops, located), (parent_ops, parent_located) in zip(with_scopes,
                                                            without):
        assert ops == parent_ops
        assert ".mixer" in located and ".ffn" in located
        assert ".mixer" not in parent_located
        assert "block_0" in parent_located

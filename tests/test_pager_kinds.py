"""The pager the scheduler builds from a model, for the five decoders
the benchmark holds (the toy configurations of
``tests/test_serving_programs_pinned.py``): the kind of page, the
pool's arrays and every size the scheduler takes from the kind are
what the scheduler of the commit before PR 49 worked out itself
(written out from a run of that commit), and each option the kind
refuses raises with its reason, in that commit's words.
"""
import copy
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

_SNAPSHOTS = (": a recurrent state cannot be adopted at a page boundary "
              "nor rolled back after a rejected draft; both need an index "
              "of state snapshots, which this scheduler does not keep")

#: cell -> what the parent's scheduler gave its pager (4 slots, block
#: 16, max_context 128), and option -> the ValueError it raised
EXPECTED = {
    "mistral7b.chat-saturated": dict(
        kind="PagedKV", pool=[((2, 33, 16, 2, 64), "float32")],
        max_pages_per_seq=8, prefill_chunk=None, carried=[],
        state_bytes_per_slot=0, ring=0, recurrent=False, refuses={}),
    "brumby14b.decode-saturated": dict(
        kind="PagedState",
        pool=[((2, 5, 2, 640, 32), "float32"),
              ((2, 5, 2, 32, 32), "float32")],
        max_pages_per_seq=1, prefill_chunk=128,
        carried=[((2, 128, 2, 32), "float32"),
                 ((2, 128, 2, 32), "float32"), ((2, 128, 2), "float32")],
        state_bytes_per_slot=557568, ring=0, recurrent=True,
        refuses={
            "prefix_sharing": "prefix_sharing with "
                              "mixer='power_retention'" + _SNAPSHOTS,
            "spec_k": "spec_k with mixer='power_retention'" + _SNAPSHOTS,
            "cache_quant": "a recurrent-state pool is float32: "
                           "cache_quant does not apply to it"}),
    "deepseekv3.decode-saturated": dict(
        kind="PagedLatent", pool=[((3, 33, 16, 128), "float32")],
        max_pages_per_seq=8, prefill_chunk=None, carried=[],
        state_bytes_per_slot=0, ring=0, recurrent=False,
        refuses={
            "prefix_sharing": "prefix_sharing with mixer='latent': its "
                              "multi-row suffix prefill reads KV heads",
            "spec_k": "spec_k with mixer='latent': the verify step's "
                      "multi-row read has no absorbed form yet",
            "cache_quant": "cache_quant with mixer='latent': a latent "
                           "row has no int8 form yet"}),
    "granite4h.chat-saturated": dict(
        kind="PagedHybrid",
        pool=[((1, 33, 16, 2, 32), "float32"),
              ((5, 5, 16, 128), "float32"), ((5, 5, 480), "float32")],
        max_pages_per_seq=8, prefill_chunk=16, carried=[],
        state_bytes_per_slot=101120, ring=0, recurrent=False,
        refuses={
            "prefix_sharing": "prefix_sharing with mixer='hybrid'"
                              + _SNAPSHOTS,
            "spec_k": "spec_k with mixer='hybrid'" + _SNAPSHOTS,
            "cache_quant": "a hybrid pool holds float KV pages beside "
                           "float32 state pages: cache_quant, state_rows "
                           "and latent_dim do not apply"}),
    "smallthinker21b.longmix-saturated": dict(
        kind="PagedWindowed",
        pool=[((1, 33, 32, 32), "float32"), ((3, 13, 32, 32), "float32")],
        max_pages_per_seq=8, prefill_chunk=None, carried=[],
        state_bytes_per_slot=0, ring=3, recurrent=False,
        refuses={
            "prefix_sharing": "prefix_sharing with windowed layers: a "
                              "shared page of a window layer would be "
                              "overwritten by its first owner's ring",
            "spec_k": "spec_k with windowed layers: a rejected draft's "
                      "row may already have overwritten a ring page a "
                      "later query sees",
            "cache_quant": "cache_quant with windowed layers: a ring "
                           "page has no int8 form yet"}),
}


def _toy(cell: str):
    """The cell's toy model and its gateway's sizes (the benchmark's
    own rehearsal sizes: its conftest, by path)."""
    from benchmarks import run
    toy = importlib.util.spec_from_file_location(
        "benchmarks_tests_conftest",
        ROOT / "benchmarks" / "tests" / "conftest.py")
    rehearsal = importlib.util.module_from_spec(toy)
    toy.loader.exec_module(rehearsal)
    spec = rehearsal.toy_spec(cell)
    built = run.Context.plugin("models", spec["config"]["builder"]).build(
        spec["config"], 7, lambda w: None)
    gw = spec["workload"]["driver_params"]["gateway"]
    return built["model"], built["net"], dict(
        max_slots=gw["max_slots"], block=gw.get("block", 16),
        max_context=gw["max_context"])


@pytest.mark.parametrize("cell", sorted(EXPECTED))
def test_the_scheduler_s_pager_is_the_parent_s(cell):
    from deeplearning4j_tpu.serving import DecodeScheduler, kv_pager
    model, net, sizes = _toy(cell)
    want = EXPECTED[cell]
    sched = DecodeScheduler(model, net, **sizes)
    pager = sched.pager
    said = lambda arrays: [(a.shape, str(a.dtype)) for a in arrays]
    assert pager.cache is getattr(kv_pager, want["kind"])
    assert said(pager.pool) == want["pool"]
    assert sched.max_pages_per_seq == want["max_pages_per_seq"]
    assert sched._page_table.shape == (4, want["max_pages_per_seq"])
    assert sched.prefill_chunk == want["prefill_chunk"]
    assert said(sched._prefill_hist) == want["carried"]
    assert pager.state_bytes_per_slot == want["state_bytes_per_slot"]
    assert (pager.ring, pager.rings) == (want["ring"],
                                         4 if want["ring"] else 0)
    assert sched.recurrent is want["recurrent"]
    quantised = copy.copy(model)    # the model's own check aside
    quantised.cache_quant = "int8"
    for option, (served, kw) in {
            "prefix_sharing": (model, dict(prefix_sharing=True)),
            "spec_k": (model, dict(spec_k=2)),
            "cache_quant": (quantised, {})}.items():
        if option not in want["refuses"]:
            DecodeScheduler(served, net, **sizes, **kw)
            continue
        with pytest.raises(ValueError) as refused:
            DecodeScheduler(served, net, **sizes, **kw)
        assert str(refused.value) == want["refuses"][option]

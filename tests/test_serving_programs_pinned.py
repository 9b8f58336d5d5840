"""The serving programs of the six decoders the benchmark holds,
pinned by the sha256 of their lowered text.

A PR that adds a kind of layer, page or expert rule beside these must
leave their step and prefill (or chunk) programs as they were: that is
how it can say that no existing cell moves before a chip has run one.
The hashes below are what the commit before PR 46 lowered, for the
benchmark's toy configurations through the real builders and
``DecodeScheduler`` (the code paths are the widths' own), once as the
CPU lowers them (the plain fallbacks) and once with the kernels forced
(``DL4J_TPU_KERNEL_FORCE=1``: what a TPU traces, in interpret mode).
PR 46's own programs lower to the same text.

A PR that MEANS to change one of these programs replaces its hashes
here, in plain sight, and says so in ``CHANGES.md``; the text carries
no source locations, so moving code changes nothing.

What the toy widths cannot show: ``ops.moe.layer`` routes a bucket of
more than ``ROUTE_BLOCK`` (4,096) rows a block at a time, and no toy
bucket is that long. Neither is the DeepSeek cell's longest (4,096,
not above it), so that branch is not taken in any cell pinned here.

Nor do they hold either page walk: a toy head is 16 to 32 wide and a
toy latent 32, under the lines ``_use_paged_kernel`` and
``_use_latent_kernel`` draw, so every toy step attends through the
plain fallbacks in both modes (PR 48 rewrote ``_paged_decode_kernel``
and no hash above moved). The two kernels are therefore pinned ALONE
(``KERNELS_ALONE``), lowered in interpret mode at the smallest shapes
their lines take: the latent kernel's text is PR 40's (PR 48 moved its
issue, wait and search loops into helpers it shares with the KV walk,
and its text did not change), the KV walk's is PR 48's. The
SmallThinker cell's programs (PR 46, with PR 47's expert kernel not in
them: toy experts are not lane-aligned) joined ``PINNED`` in PR 48.
PR 50 gave the two kinds of softmax layer a head count and a rotary
rule each and the attention an output gate (``decoder_infer.qkv``,
``block``, ``Turns``; ``PagedKV.attend``), and no hash of the five
earlier decoders moved: a decoder with one head count, one plain
rule and no gate traces none of it. Its own cell's programs (the
gated windowed decoder) are pinned from PR 50 on.
PR 51 MEANT to change the bucket prefills of the four decoders that
admit by buckets, with the kernels forced: their attention is the
flash kernel's causal inference path now (``_prefill_kernel``: the
key loop inside the kernel, the prompt's length an operand), so the
eight ``admit16`` / ``admit64`` hashes under ``kernels`` are PR 51's
(a toy bucket's ONE 128-key block is its own half, here as on the
chip, and is walked by the kernel's loop of whole blocks).
Every step, every chunk program and every ``plain`` program kept its
text: the einsum path is handed no length.
"""
import hashlib
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: cell -> mode -> program -> sha256[:16] of ``lower().as_text()``
PINNED = {
    "mistral7b.chat-saturated": {
        "plain": {"step": "c1504e32290e447a", "admit16": "8942b110f3b5152a",
                  "admit64": "85305781cd81e6a6"},
        "kernels": {"step": "f05e0258633bcbd1",
                    "admit16": "21b442f67e06be11",
                    "admit64": "e65e34e730125d91"}},
    "brumby14b.decode-saturated": {
        "plain": {"step": "10bffa502cea6987", "chunk": "1e9c9d88a2f52f80"},
        "kernels": {"step": "4aeb03494500f1bf",
                    "chunk": "e9ce8c0d1a36f05a"}},
    "deepseekv3.decode-saturated": {
        "plain": {"step": "aa9309f15c76f849", "admit16": "688578e8ff9f0e2c",
                  "admit64": "cf8aaf7566861420"},
        "kernels": {"step": "9d1f7fde90657376",
                    "admit16": "6782de41d4866d5a",
                    "admit64": "c63ca8828efb0679"}},
    "granite4h.chat-saturated": {
        "plain": {"step": "ccc14242d5877cf1", "chunk": "9ba93a13f711d334"},
        "kernels": {"step": "8ae93b0aba4ee239",
                    "chunk": "ff401f5cc2fdf0cb"}},
    "smallthinker21b.longmix-saturated": {
        "plain": {"step": "44b7c200447d1595", "admit16": "569daaa68cfb95fa",
                  "admit64": "2a49ae68fab2c748"},
        "kernels": {"step": "28a8d40a4afd12b5",
                    "admit16": "266ffac52296ed09",
                    "admit64": "a630e7e39d8d1d3a"}},
    "lagunaxs2.agent-saturated": {
        "plain": {"step": "ea01ab8afcc793a5", "admit16": "a5376d17a1147d77",
                  "admit64": "99cac54177b4dcd8"},
        "kernels": {"step": "a440c7e9228865bb",
                    "admit16": "38b9427e4fcadf46",
                    "admit64": "048417f8dbdfaffd"}},
}

#: kernel form -> sha256[:16] of its jitted call's lowered text, in
#: interpret mode, four slots
KERNELS_ALONE = {
    "latent": "69aa35110c121500",
    "latent_bf16_chunk4": "e5aebe3f91eae05f",
    "paged": "eac4a722f7e6d094",
    "paged_window_folded": "0e97385756935728",
    "paged_heads_of_64": "72de30ede60e435f",
}


def _sha(lowered) -> str:
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def _programs(cell: str) -> dict:
    from benchmarks import run
    from deeplearning4j_tpu.serving import DecodeScheduler
    # the benchmark's own rehearsal sizes (its conftest, by path: the
    # name is this directory's too)
    toy = importlib.util.spec_from_file_location(
        "benchmarks_tests_conftest",
        ROOT / "benchmarks" / "tests" / "conftest.py")
    rehearsal = importlib.util.module_from_spec(toy)
    toy.loader.exec_module(rehearsal)
    spec = rehearsal.toy_spec(cell)
    cfg = spec["config"]
    built = run.Context.plugin("models", cfg["builder"]).build(
        cfg, 7, lambda w: None)
    model, net = built["model"], built["net"]
    gw = spec["workload"]["driver_params"]["gateway"]
    sched = DecodeScheduler(model, net, max_slots=gw["max_slots"],
                            block=gw.get("block", 16),
                            max_context=gw["max_context"])
    params = model.decode_params(net)
    sds, i32, f32 = jax.ShapeDtypeStruct, jnp.int32, jnp.float32
    pool = tuple(sds(a.shape, a.dtype) for a in sched.pager.pool)
    scalars = (sds((), i32), sds((), f32), sds((), f32), sds((), i32))
    out = {"step": _sha(sched._step_fn.lower(
        params, pool, *sched._step_feed_shapes()))}
    if sched._chunk_fn is not None:
        out["chunk"] = _sha(sched._chunk_fn.lower(
            params, pool,
            tuple(sds(a.shape, a.dtype) for a in sched._prefill_hist),
            sched._chunk_where_shapes(),
            sds((1, sched.prefill_chunk), i32), sds((), i32), *scalars))
    else:
        for tb in (16, 64):
            out[f"admit{tb}"] = _sha(sched._admit_fn(tb).lower(
                params, pool, sched.pager.prompt_pages_shapes(tb),
                sds((1, tb), i32), *scalars))
    return out


@pytest.mark.parametrize("mode", ["plain", "kernels"])
@pytest.mark.parametrize("cell", sorted(PINNED))
def test_the_earlier_decoders_programs_lower_to_the_pinned_text(
        monkeypatch, cell, mode):
    if mode == "kernels":
        monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    assert _programs(cell) == PINNED[cell][mode]


def _kernel_alone(form: str):
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    sds, i32, f32, s_, mp = jax.ShapeDtypeStruct, jnp.int32, jnp.float32, 4, 6
    feed = lambda pages: (sds((), i32), sds((s_, pages), i32),
                          sds((s_,), i32))
    if form.startswith("latent"):
        dt, chunk = ((jnp.bfloat16, 4) if form.endswith("chunk4")
                     else (f32, 2))
        return pk._latent_decode_call.lower(
            sds((s_, 16, 256), dt), sds((2, 1 + s_ * mp, 16, 256), dt),
            *feed(mp), scale=0.11, kv_rank=128, pages_per_chunk=chunk,
            interpret=True)
    if form == "paged_window_folded":       # a ring of 3, 4 kv heads
        return pk._paged_decode_call.lower(
            sds((s_, 8, 128), f32), sds((2, 1 + s_ * 3, 64, 256), f32),
            *feed(3), pages_per_chunk=2, interpret=True, window=32,
            n_kv=4)
    d = 64 if form == "paged_heads_of_64" else 128
    return pk._paged_decode_call.lower(
        sds((s_, 32, d), f32), sds((2, 1 + s_ * mp, 16, 8, 2 * d), f32),
        *feed(mp), pages_per_chunk=2, interpret=True)


@pytest.mark.parametrize("form", sorted(KERNELS_ALONE))
def test_the_page_walks_lower_to_the_pinned_text(form):
    assert _sha(_kernel_alone(form)) == KERNELS_ALONE[form]

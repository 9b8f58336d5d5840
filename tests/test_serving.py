"""Continuous-batching serving gateway (serving/ — ISSUE 13).

The three fences this file owns:

- **pager correctness**: paged decode (float AND int8 pages) is
  TOKEN-IDENTICAL to dense ``generate()`` for the same prompts/seed —
  continuous batching must never change what a request returns;
- **pager invariants**: no page owned by two live sequences, free-list
  conservation under admit/evict churn, trash page out of circulation;
- **serving semantics**: fixed-shape zero-retrace decode after
  warmup, admission control on free pages, queue-full/deadline
  shedding, graceful drain, tenant fairness, fault-shed without a
  wedged slot or leaked page, and the continuous-vs-request-at-a-time
  throughput acceptance.
"""
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.parallel.inference import (DeadlineExpiredError,
                                                   QueueFullError,
                                                   ServingShutdownError)
from deeplearning4j_tpu.serving import (DecodeScheduler, KVPager,
                                        PageTableError, SequenceAborted,
                                        ServingGateway)
from deeplearning4j_tpu.zoo import GPTNano
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM, prompt_bucket


def _tiny_model(**kw):
    """2-layer/32-hidden LM: fast compiles for the scheduling tests
    (the identity fences use GPTNano to cover GQA + 4 layers)."""
    kw.setdefault("vocab_size", 64)
    return CausalTransformerLM(hidden=32, n_layers=2, n_heads=2,
                               n_kv_heads=1,
                               max_len=kw.pop("max_len", 64),
                               seed=kw.pop("seed", 9), **kw)


@pytest.fixture(scope="module")
def tiny():
    model = _tiny_model()
    return model, model.init()


class _Req:
    """Minimal duck-typed request for driving DecodeScheduler
    directly (no gateway thread — deterministic churn tests)."""

    def __init__(self, prompt, max_new, temperature=None, eos_id=None):
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new = max_new
        self.temperature = temperature
        self.eos_id = eos_id
        self.tokens = []
        self.done = False
        self.error = None

    def push(self, tok):
        self.tokens.append(int(tok))

    def finish(self):
        self.done = True

    def fail(self, e):
        self.error = e
        self.done = True


class _EosReq(_Req):
    """A request that ends by ``eos_id`` at its ``stop_at``-th token,
    whatever that token is: the scheduler compares each token with
    ``eos_id`` after pushing it, and cannot know before it has read
    the step (a budget it knows at launch)."""

    def __init__(self, prompt, max_new, stop_at, **kw):
        super().__init__(prompt, max_new, **kw)
        self.stop_at = stop_at

    @property
    def eos_id(self):
        return (self.tokens[-1] if len(self.tokens) == self.stop_at
                else None)

    @eos_id.setter
    def eos_id(self, _):
        pass


def _drive(sched, plan, iters, drained):
    """A scripted schedule on the scheduler alone: ``plan[it]`` is
    admitted before iteration ``it``'s step. ``drained`` reads every
    step before the next is launched (the order the loop had before
    it kept a step in flight)."""
    for it in range(iters):
        for r in plan.get(it, ()):
            assert sched.admit(r)
        sched.step()
        if drained:
            sched.drain()
    while sched.active_count() or sched._inflight is not None:
        sched.step()
    sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1


# =========================================================================
# pager-correctness fence: paged decode == dense generate(), token for
# token (float and int8 pages), across staggered admissions
# =========================================================================

# int8 rides the slow lane (~17s of fresh-GPTNano compiles vs tier-1's
# 870s wall-clock budget); tier-1 int8 paged identity stays fenced by
# test_int8_pages_roundtrip_token_for_token
@pytest.mark.parametrize("cache_quant", [
    None, pytest.param("int8", marks=pytest.mark.slow)])
def test_paged_decode_token_identical_to_dense(cache_quant):
    model = GPTNano(vocab_size=64, max_len=64, seed=7,
                    cache_quant=cache_quant)
    net = model.init()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, t).astype(np.int32)
               for t in (5, 17, 9, 30, 3, 22)]
    budgets = [10, 4, 16, 8, 12, 6]
    dense = [np.asarray(model.generate(net, p[None], n_new=n))[0]
             for p, n in zip(prompts, budgets)]
    # 3 slots for 6 requests: admissions stagger mid-decode, every
    # slot serves sequences at different positions/buckets — the
    # continuous batch must still reproduce every dense output exactly
    gw = ServingGateway(model, net, max_slots=3, block=8,
                        max_context=64)
    gw.warmup(prompt_lens=(3, 5, 9, 17, 22, 30))
    streams = [gw.submit(p, max_new=n)
               for p, n in zip(prompts, budgets)]
    for st, d in zip(streams, dense):
        got = st.result(timeout=120)
        np.testing.assert_array_equal(got, d)
    gw._sched.pager.check_invariants()
    assert gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1
    gw.shutdown()


def test_pool_holds_positions_in_order_after_prefill_and_decode():
    """The layout fence: what ``serving.prefill`` scatters into pages
    and what each decode step appends, read back through the slot's
    page-table row as ``[position, kv head, K|V]``, is the dense cache
    of the same tokens, position for position, in every layer."""
    import jax.numpy as jnp
    model = GPTNano(vocab_size=64, max_len=64, seed=7)
    net = model.init()
    prompt = np.random.default_rng(3).integers(0, 64, 11).astype(np.int32)
    sched = DecodeScheduler(model, net, max_slots=2, block=8,
                            max_context=64)
    r = _Req(prompt, 9)
    assert sched.admit(r)
    pages = list(sched.pager.owned(r))
    for _ in range(7):                      # crosses into a new page
        sched.step()
    (kv,) = sched.pager.pool
    n = 11 + 7                              # positions written so far
    assert kv.shape[2:] == (8, model.n_kv_heads,
                            2 * model.hidden // model.n_heads)
    got = np.asarray(kv[:, np.asarray(pages)]).reshape(
        model.n_layers, -1, *kv.shape[3:])[:, :n]
    toks = np.concatenate([prompt, np.asarray(r.tokens[:7], np.int32)])
    pad = np.zeros((1, 32), np.int32)
    pad[0, :n] = toks
    _, caches = model._prefill_forward(
        model.decode_params(net), jnp.asarray(pad), 32,
        jnp.asarray(n, jnp.int32))
    # dense cache: [1, Hkv, 2D, T] a layer -> [T, Hkv, 2D]
    want = np.stack([np.asarray(c)[0].transpose(2, 0, 1)[:n]
                     for c in caches])
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the trash page took nothing but inactive slots' writes, and no
    # page outside the reservation was touched
    other = np.setdiff1d(np.arange(1, sched.pager.n_pages), pages)
    assert not np.asarray(kv[:, other]).any()


def test_decode_step_record_counts_the_pages_walked(tiny):
    """``kv_pages`` on every ``serving.decode_step`` record, and the
    gauge beside the occupancy gauge: sum(ceil(length / block)) over
    the active slots, the position being written included."""
    from deeplearning4j_tpu import obs
    model, net = tiny
    sched = DecodeScheduler(model, net, max_slots=3, block=8,
                            max_context=64)
    rng = np.random.default_rng(1)
    reqs = [_Req(rng.integers(0, 64, t).astype(np.int32), n)
            for t, n in ((7, 12), (16, 3), (25, 9))]
    for r in reqs:
        assert sched.admit(r)
    mark = obs.now()
    seen = 0
    while any(not r.done for r in reqs):
        act = [i for i, sl in enumerate(sched._slots) if sl is not None]
        want = sum(-(-(int(sched._lengths[i]) + 1) // 8) for i in act)
        sched.step()
        sched.drain()       # the mirror level again before it is read
        rec = [e for e in obs.trace.records(since=mark)
               if e.name == "serving.decode_step"][-1]
        assert rec.counts["kv_pages"] == want, (rec.counts, want)
        assert rec.counts["active"] == len(act)
        assert obs.metrics.SERVING_KV_WALKED.snapshot()[""] == want
        seen += 1
    assert seen >= 11


def test_paged_kernel_in_the_step_token_identical_to_dense(monkeypatch):
    """The decode step with the KERNEL in it (forced, interpret mode;
    8 kv heads of 128 lanes put the shape over the dispatch line)
    against dense ``generate()``, float32, staggered admissions."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
    calls = []
    real = pk._paged_decode_call
    monkeypatch.setattr(
        pk, "_paged_decode_call",
        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    model = CausalTransformerLM(vocab_size=64, hidden=1024, n_layers=2,
                                n_heads=8, n_kv_heads=8, max_len=64,
                                ffn_mult=1, seed=4)
    net = model.init()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 64, t).astype(np.int32)
               for t in (5, 17, 9)]
    budgets = [9, 4, 12]
    dense = [np.asarray(model.generate(net, p[None], n_new=n))[0]
             for p, n in zip(prompts, budgets)]
    sched = DecodeScheduler(model, net, max_slots=2, block=8,
                            max_context=64)
    reqs = [_Req(p, n) for p, n in zip(prompts, budgets)]
    waiting = list(reqs)
    while waiting or sched.active_count():
        while waiting and sched.can_admit(len(waiting[0].prompt),
                                          waiting[0].max_new):
            assert sched.admit(waiting.pop(0))
        sched.step()
    assert len(calls) == model.n_layers     # traced once: one a layer
    for r, p, d in zip(reqs, prompts, dense):
        np.testing.assert_array_equal(
            np.concatenate([p, np.asarray(r.tokens, np.int32)]), d)
    sched.pager.check_invariants()


def test_bucket_prefill_by_the_flash_kernel_equals_the_einsum_path(
        admits_alike_by_einsum_and_kernel):
    """Full softmax layers (4 heads over 2 kv heads): the admission
    hands the kernel the prompt's length."""
    model = CausalTransformerLM(vocab_size=64, hidden=64, n_layers=2,
                                n_heads=4, n_kv_heads=2, max_len=128,
                                seed=5)
    admits_alike_by_einsum_and_kernel(model, model.init(), block=8,
                                      max_context=128)


# =========================================================================
# pager invariants
# =========================================================================

def test_pager_alloc_release_conservation():
    pager = KVPager(n_layers=2, n_kv_heads=1, head_dim=16, n_pages=9,
                    block=8, cache_quant=None)
    a, b = object(), object()
    pa = pager.alloc(3, a)
    pb = pager.alloc(4, b)
    assert len(pa) == 3 and len(pb) == 4
    assert 0 not in pa + pb                  # trash page reserved
    assert not set(pa) & set(pb)             # disjoint owners
    assert pager.free_pages() == 1
    assert pager.alloc(2, object()) is None  # exhausted -> refused
    assert pager.free_pages() == 1           # refusal takes nothing
    pager.check_invariants()
    assert pager.release(a) == 3
    assert pager.free_pages() == 4
    assert pager.release(b) == 4
    assert pager.free_pages() == 8           # full conservation
    pager.check_invariants()


def test_pager_detects_double_ownership():
    pager = KVPager(n_layers=1, n_kv_heads=1, head_dim=8, n_pages=5,
                    block=8, cache_quant=None)
    a, b = object(), object()
    pa = pager.alloc(2, a)
    pager.alloc(1, b)
    # corrupt the table the way a scheduler bug would
    pager._pages_of[id(b)].append(pa[0])
    with pytest.raises(PageTableError, match="two live sequences"):
        pager.check_invariants()


def test_pager_invariants_under_admit_evict_churn(tiny):
    """Seeded random admit/step/evict churn with the invariant check
    after EVERY transition: no shared pages, no leaks, full free-list
    conservation once drained."""
    model, net = tiny
    sched = DecodeScheduler(model, net, max_slots=3, block=8,
                            max_context=32, n_pages=10)
    sched.warmup(prompt_lens=range(1, 17))
    rng = np.random.default_rng(4)
    live = []
    for it in range(120):
        op = rng.integers(0, 3)
        if op == 0:
            r = _Req(rng.integers(0, 64, int(rng.integers(1, 17))),
                     int(rng.integers(1, 9)))
            if sched.can_admit(r.prompt.size, r.max_new):
                assert sched.admit(r)
                if not r.done:
                    live.append(r)
        elif op == 1:
            sched.step()
        elif live:
            sched.evict(live.pop(int(rng.integers(0, len(live)))))
        live = [r for r in live if not r.done]
        sched.pager.check_invariants()
    while any(s is not None for s in sched._slots):
        sched.step()
        sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1


def test_int8_pages_roundtrip_token_for_token(tiny):
    """Satellite: int8 page storage must reproduce the dense int8-KV
    decode path token-for-token on a fixed seed (the quantiser is
    shared — ``quant_kv`` — so codes and scales are bit-equal)."""
    model = _tiny_model(cache_quant="int8", seed=11)
    net = model.init()
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 64, 13).astype(np.int32)
    dense = np.asarray(model.generate(net, prompt[None], n_new=14))[0]
    sched = DecodeScheduler(model, net, max_slots=2, block=8,
                            max_context=64)
    sched.warmup(prompt_lens=(13,))
    r = _Req(prompt, 14)
    assert sched.admit(r)
    while not r.done:
        sched.step()
    np.testing.assert_array_equal(
        np.concatenate([prompt, np.asarray(r.tokens, np.int32)]),
        dense)


# =========================================================================
# fixed-shape contract: zero retraces after warmup
# =========================================================================

def test_zero_retraces_after_warmup(tiny):
    from deeplearning4j_tpu.perf import sentry
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=3, block=8,
                        max_context=32, default_max_new=6)
    gw.warmup(prompt_lens=range(1, 25))
    before = sentry.total_traces()
    rng = np.random.default_rng(1)
    with sentry.strict():
        streams = [gw.submit(rng.integers(0, 64, int(t)), max_new=6)
                   for t in rng.integers(1, 25, 12)]
        for st in streams:
            st.result(timeout=120)
    assert sentry.total_traces() == before, \
        "continuous-batching traffic retraced after warmup"
    gw.shutdown()


def test_gateway_and_generate_share_bucket_table():
    """Satellite: the gateway's prefill buckets come from the same
    module-level helper generate()/warmup_decode use — drift here
    would be a guaranteed retrace on the first live request."""
    model = _tiny_model()
    assert prompt_bucket(5) == 16
    assert prompt_bucket(17) == 32
    assert prompt_bucket(40, 48) == 48          # max_len clamp
    net = model.init()
    sched = DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=64)
    warm = sched.warmup(prompt_lens=range(1, 33))
    want = sorted({prompt_bucket(t, 64) for t in range(1, 33)})
    assert warm["buckets"] == want


# =========================================================================
# gateway serving semantics (shed / deadline / drain / fairness)
# =========================================================================

def test_queue_full_sheds_fast(tiny):
    from deeplearning4j_tpu.obs import metrics
    model, net = tiny
    # worker never started: the queue fills deterministically
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, queue_limit=3,
                        default_max_new=4, start=False)
    p = np.zeros(4, np.int32)
    for _ in range(3):
        gw.submit(p)
    t0 = time.perf_counter()
    with pytest.raises(QueueFullError):
        gw.submit(p)
    assert time.perf_counter() - t0 < 0.5       # shed, not blocked
    shed = metrics.SERVING_SHED.labels(reason="queue_full")
    assert shed.get() >= 1


def test_deadline_sheds_unadmitted_requests(tiny):
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=1, block=8,
                        max_context=64, default_max_new=4)
    gw.warmup(prompt_lens=(4,))
    blocker = gw.submit(np.zeros(4, np.int32), max_new=40)
    # explicit 0 deadline = already expired (the `is not None`
    # falsy-deadline contract): must shed, never serve
    doomed = gw.submit(np.zeros(4, np.int32), deadline_s=0.0)
    with pytest.raises(DeadlineExpiredError):
        doomed.result(timeout=30)
    assert blocker.result(timeout=120).shape == (44,)
    gw.shutdown()


def test_shutdown_drains_inflight_and_flushes_queue(tiny):
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=1, block=8,
                        max_context=64, default_max_new=24)
    gw.warmup(prompt_lens=(4,))
    running = gw.submit(np.zeros(4, np.int32))
    # wait until it is admitted (first token streamed)
    for _ in range(500):
        if running.n_generated():
            break
        time.sleep(0.01)
    queued = [gw.submit(np.zeros(4, np.int32)) for _ in range(2)]
    dropped = gw.shutdown(drain=True)
    assert dropped == 2
    assert running.result(timeout=30).shape == (28,)  # drained to end
    for st in queued:
        with pytest.raises(ServingShutdownError):
            st.result(timeout=5)
    with pytest.raises(ServingShutdownError):
        gw.submit(np.zeros(4, np.int32))
    assert gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1


def test_tenant_round_robin_fairness(tiny):
    """One chatty tenant must not starve another: with one slot, a
    flood from tenant A and a late pair from tenant B interleave, so
    both B requests serve before A's tail."""
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=1, block=8,
                        max_context=32, default_max_new=8,
                        queue_limit=32, start=False)
    a = [gw.submit(np.zeros(3, np.int32), tenant="A")
         for _ in range(6)]
    b = [gw.submit(np.zeros(3, np.int32), tenant="B")
         for _ in range(2)]
    gw.warmup(prompt_lens=(3,))
    gw._worker = threading.Thread(target=gw._loop, daemon=True)
    gw._worker.start()
    for st in a + b:
        st.result(timeout=120)
    # admission order == TTFT order with one slot
    t_first = {st: st.t_first for st in a + b}
    assert max(t_first[st] for st in b) < max(t_first[st] for st in a[3:])
    gw.shutdown()


def test_admission_control_on_free_pages(tiny):
    """Pool smaller than the offered load: admission defers until
    pages free up, every request still completes, nothing leaks."""
    model, net = tiny
    # 7 usable pages; each request needs ceil(max(16, 3+11)/8)=2 pages
    # -> at most 3 in flight despite 4 slots
    gw = ServingGateway(model, net, max_slots=4, block=8,
                        max_context=32, n_pages=8, default_max_new=12,
                        queue_limit=32)
    gw.warmup(prompt_lens=(3,))
    streams = [gw.submit(np.zeros(3, np.int32)) for _ in range(10)]
    for st in streams:
        assert st.result(timeout=120).shape == (15,)
    gw._sched.pager.check_invariants()
    assert gw._sched.pager.free_pages() == 7
    gw.shutdown()


def test_oversized_request_fails_loudly(tiny):
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, n_pages=3, start=False)
    with pytest.raises(ValueError, match="pages"):
        gw.submit(np.zeros(20, np.int32), max_new=12)
    with pytest.raises(ValueError, match="max_context"):
        gw.submit(np.zeros(30, np.int32), max_new=8)
    with pytest.raises(ValueError, match="empty"):
        gw.submit(np.zeros(0, np.int32))


def test_streaming_tokens_and_eos(tiny):
    model, net = tiny
    sched = DecodeScheduler(model, net, max_slots=2, block=8,
                            max_context=32)
    sched.warmup(prompt_lens=(5,))
    probe = _Req(np.arange(5), 6)
    sched.admit(probe)
    while not probe.done:
        sched.step()
    assert len(probe.tokens) == 6
    # eos: same prompt with eos_id = the 3rd token it will produce
    # stops there and frees the pages
    eos = probe.tokens[2]
    if eos not in probe.tokens[:2]:         # unambiguous cut point
        r = _Req(np.arange(5), 6, eos_id=eos)
        sched.admit(r)
        while not r.done:
            sched.step()
        assert r.tokens == probe.tokens[:3]
    sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1

    # gateway streaming surface: tokens() yields the same sequence
    # result() returns
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=6)
    gw.warmup(prompt_lens=(5,))
    st = gw.submit(np.arange(5, dtype=np.int32))
    toks = list(st.tokens(timeout=60))
    np.testing.assert_array_equal(
        st.result(timeout=5), np.concatenate([np.arange(5), toks]))
    assert toks == probe.tokens
    gw.shutdown()


def test_cancel_queued_and_live_sequences(tiny):
    """The cancel path is a slot/page-freeing path like retire and
    shed: cancelling one QUEUED stream and one MID-GENERATION stream
    must finish both without error, release every page, and leave the
    remaining traffic serving."""
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=1, block=8,
                        max_context=32, default_max_new=16)
    gw.warmup(prompt_lens=(4,))
    live = gw.submit(np.zeros(4, np.int32))
    for _ in range(500):                      # wait until admitted
        if live.n_generated():
            break
        time.sleep(0.005)
    queued = gw.submit(np.zeros(4, np.int32))
    survivor = gw.submit(np.zeros(4, np.int32), max_new=4)
    assert gw.cancel(queued)                  # unqueued immediately
    assert gw.cancel(live)                    # evicted by the worker
    assert queued.result(timeout=10).shape == (4,)   # no tokens, no error
    partial = live.result(timeout=30)
    assert live.error() is None and partial.shape[0] < 20
    assert survivor.result(timeout=60).shape == (8,)
    gw._sched.pager.check_invariants()
    assert gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1
    gw.shutdown()


def test_sampled_decoding_serves_without_retraces(tiny):
    from deeplearning4j_tpu.perf import sentry
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=6,
                        sample=True, top_k=8, top_p=0.9, seed=3)
    gw.warmup(prompt_lens=(4, 20))
    before = sentry.total_traces()
    outs = []
    for t in (4, 17):
        st = gw.submit(np.zeros(t, np.int32), temperature=0.8)
        outs.append(st.result(timeout=120))
    assert sentry.total_traces() == before
    for t, o in zip((4, 17), outs):
        gen = o[t:]
        assert gen.shape == (6,)
        assert ((gen >= 0) & (gen < model.vocab_size)).all()
    gw.shutdown()


# =========================================================================
# fault path: shed-not-wedge, no leaked pages (chaos.py drills the
# same site end-to-end)
# =========================================================================

def test_injected_fault_sheds_inflight_and_recovers(tiny):
    from deeplearning4j_tpu.obs import metrics
    from deeplearning4j_tpu.resilience import faults
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=64, default_max_new=30,
                        queue_limit=16)
    gw.warmup(prompt_lens=(4,))
    shed0 = metrics.SERVING_SHED.labels(reason="fault").get()
    with faults.active("serving:error=RuntimeError:nth=3:max=1"):
        # two different prompts -> different token streams: each
        # victim's structured error must carry ITS OWN tokens (a
        # shared exception instance leaked the first stream's tokens
        # into every other client's error)
        victims = [gw.submit(np.full(4, i, np.int32))
                   for i in range(2)]
        errors = 0
        for st in victims:
            try:
                st.result(timeout=60)
            except SequenceAborted as e:
                errors += 1
                assert e.tokens, "structured error carries the " \
                                 "tokens streamed before the fault"
                assert e.tokens == st._tokens, \
                    "cross-request token leakage in shed error"
        assert errors == 2
        assert victims[0]._tokens != victims[1]._tokens
        fired = sum(s["fires"] for s in faults.stats().values())
    assert fired == 1
    assert metrics.SERVING_SHED.labels(reason="fault").get() \
        == shed0 + 2
    # never a wedged slot or leaked page: pool is whole and the SAME
    # worker serves the next request
    gw._sched.pager.check_invariants()
    assert gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1
    post = gw.submit(np.zeros(4, np.int32), max_new=4)
    assert post.result(timeout=60).shape == (8,)
    gw.shutdown()


def test_starved_large_request_ages_into_admission(tiny):
    """Anti-starvation aging: a page-hungry request must not wait
    forever while smaller arrivals keep taking every freed page —
    past ``starvation_patience`` the oldest head blocks younger
    admissions until the pool accumulates its need."""
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, n_pages=5, queue_limit=32,
                        default_max_new=12, starvation_patience=0.2)
    gw.warmup(prompt_lens=(3, 4))
    small = lambda: gw.submit(np.zeros(3, np.int32), tenant="small",
                              max_new=12)          # 2 pages
    others = [small() for _ in range(2)]           # pool now full
    big = gw.submit(np.zeros(4, np.int32), tenant="big",
                    max_new=18)                    # needs 3 pages
    others += [small() for _ in range(8)]          # sustained smalls
    assert big.result(timeout=120).shape == (22,)
    for st in others:
        st.result(timeout=120)
    # aging moved it ahead of the small-request tail
    assert big.t_first < max(st.t_first for st in others[-4:])
    gw._sched.pager.check_invariants()
    gw.shutdown()


def test_admission_fault_sheds_request_not_worker(tiny):
    """A device error during PREFILL (not just the step) must shed
    that one request with a structured error, release its page
    reservation, and leave the worker serving — the admission path is
    outside the step's try block and killed the worker before."""
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=4)
    gw.warmup(prompt_lens=(4,))
    sched = gw._sched
    real_admit_fn = sched._admit_fn
    calls = [0]

    def poisoned(tb):
        calls[0] += 1
        if calls[0] == 1:
            raise RuntimeError("synthetic prefill device error")
        return real_admit_fn(tb)

    sched._admit_fn = poisoned
    victim = gw.submit(np.zeros(4, np.int32))
    with pytest.raises(SequenceAborted, match="admission fault"):
        victim.result(timeout=30)
    # reservation released, worker alive, next request serves
    ok = gw.submit(np.zeros(4, np.int32))
    assert ok.result(timeout=60).shape == (8,)
    sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1
    gw.shutdown()


def test_zero_temperature_rejected_loudly(tiny):
    """temperature=0.0 must raise, not silently sample at 1.0 (the
    falsy-zero bug class the deadline satellite fixed)."""
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, sample=True, top_k=4,
                        start=False)
    with pytest.raises(ValueError, match="temperature"):
        gw.submit(np.zeros(4, np.int32), temperature=0.0)


# =========================================================================
# acceptance: the load generator's report + SLO export
# =========================================================================

def test_loadgen_report_completes_without_retraces_and_exports_slos(tiny):
    """Under the synthetic multi-tenant closed-loop trace
    (``loadgen.subprocess_report``: a fresh one-device CPU process,
    outside this suite's 8-virtual-device partitioning) the gateway
    completes every request with zero retraces after warmup, and the
    serving-family /metrics export is asserted in-process on a small
    trace. How much faster than request-at-a-time ``generate()`` the
    gateway is, is a number of the chip (PERF.md §5), not of a CPU
    that other test workers share: the report's ``speedup`` is not
    asserted here."""
    from deeplearning4j_tpu.obs import metrics
    from deeplearning4j_tpu.serving import loadgen

    rep = loadgen.subprocess_report()
    assert rep["platform"] == "cpu"     # a CPU number, and it says so
    assert rep["retraces_after_warmup"] == 0
    assert rep["completed"] == rep["n_requests"] and rep["failed"] == 0
    assert rep["ttft_p99_ms"] is not None

    # in-process: the SLO families flow through /metrics (the earlier
    # gateway tests produced traffic in this registry)
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=4)
    gw.warmup(prompt_lens=(4,))
    stats = loadgen.run_trace(
        gw, loadgen.gen_requests(n_requests=4, max_new=4,
                                 prompt_lens=(2, 8), vocab_size=64),
        mode="open", rate=200.0)
    gw.shutdown()
    assert stats["completed"] == 4
    fams = metrics.parse_exposition(metrics.exposition())
    names = {n for n, _ in fams}
    assert "dl4j_tpu_serving_ttft_seconds_count" in names
    assert "dl4j_tpu_serving_tokens_total" in names
    assert "dl4j_tpu_serving_kv_pages_free" in names
    assert "dl4j_tpu_serving_step_seconds_count" in names


# =========================================================================
# request-scoped serving traces (ISSUE 14 satellite): submit → admit →
# prefill → decode-steps → retire/abort as async tracks keyed by
# request id, zero events with tracing off
# =========================================================================

def test_request_traces_off_path_zero_events(tiny):
    from deeplearning4j_tpu import obs

    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=4)
    gw.warmup(prompt_lens=(4,))
    e0 = obs.trace.events_recorded()
    t0 = obs.now()
    st = gw.submit(np.arange(4, dtype=np.int32) % 64)
    st.result(timeout=60)
    gw.shutdown()
    # nothing exported: no Chrome event built, no file
    assert obs.trace.events_recorded() == e0
    assert obs.trace.trace_path() is None
    # the ring is always on: the request left exactly ONE record, with
    # its five stamps in order
    mine = [r for r in obs.trace.records(since=t0)
            if r.name == "serving.request"]
    assert len(mine) == 1 and mine[0].cause == st.rid
    assert list(mine[0].stamps) == sorted(mine[0].stamps)
    assert mine[0].stamps == (st.t_submit, st.t_admit, st.t_first,
                              st.t_last, st.t_done)
    assert mine[0].counts["tokens"] == 4


def test_worker_records_cover_its_wall_time_and_join_requests(tiny):
    """The gateway worker's records tile its thread: every iteration
    is a ``serving.loop/iter`` record (>= 95% of the thread's wall
    time between the first and the last), whose children carry its
    number; every prefill record's rid has a request record."""
    from deeplearning4j_tpu import obs

    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=6)
    gw.warmup(prompt_lens=(4,))
    t0 = obs.now()
    streams = [gw.submit(np.arange(4, dtype=np.int32) % 64,
                         tenant=f"t{i % 2}") for i in range(5)]
    for st in streams:
        st.result(timeout=60)
    gw.shutdown()
    recs = obs.trace.records(since=t0)
    iters = [r for r in recs if r.name == "serving.loop/iter"]
    assert iters and len({r.tid for r in iters}) == 1
    tid = iters[0].tid
    wall = iters[-1].stamps[-1] - iters[0].stamps[0]
    assert sum(r.stamps[-1] - r.stamps[0] for r in iters) >= 0.95 * wall
    # children lie inside the iteration that caused them
    by_iter = {r.cause: r for r in iters}
    kids = [r for r in recs if r.tid == tid and r.name in (
        "serving.loop/admit", "serving.loop/park", "serving.prefill",
        "serving.decode_step")]
    assert {r.name for r in kids} >= {"serving.loop/admit",
                                      "serving.prefill",
                                      "serving.decode_step"}
    for r in kids:
        if r.cause not in by_iter:      # an iteration cut by since=
            continue
        parent = by_iter[r.cause]
        assert parent.stamps[0] <= r.stamps[0]
        assert r.stamps[-1] <= parent.stamps[-1]
    steps = [r for r in kids if r.name == "serving.decode_step"]
    assert all(r.phases[-1] == "deliver" and len(r.stamps) == 5
               for r in steps)
    admits = [r for r in kids if r.name == "serving.loop/admit"]
    assert sum(r.counts["admitted"] for r in admits) == 5
    assert any(r.counts["active"] > 0 for r in admits)
    # requests join their prefills by rid
    done = {r.counts["rid"] for r in recs if r.name == "serving.request"}
    prefills = [r for r in recs if r.name == "serving.prefill"]
    assert len(prefills) == 5
    assert {r.counts["rid"] for r in prefills} \
        == {st.rid for st in streams} <= done


def test_request_traces_nested_phases_with_ids(tiny, tmp_path):
    from deeplearning4j_tpu import obs

    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=4)
    gw.warmup(prompt_lens=(4,))
    path = str(tmp_path / "serving_trace.jsonl")
    obs.trace.enable(path)
    try:
        streams = [gw.submit(np.arange(4, dtype=np.int32) % 64,
                             tenant=f"t{i % 2}") for i in range(3)]
        for s in streams:
            s.result(timeout=60)
    finally:
        obs.trace.disable()
    gw.shutdown()
    evs = obs.trace.read_trace(path)
    reqs = [e for e in evs
            if str(e.get("name", "")).startswith("serving.request")]
    # every phase present, as async b/e pairs sharing the request id
    by_phase = {}
    for e in reqs:
        by_phase.setdefault(e["name"], []).append(e)
    for phase in ("serving.request", "serving.request/queue_wait",
                  "serving.request/prefill",
                  "serving.request/decode_steps"):
        pair = by_phase[phase]
        assert {p["ph"] for p in pair} == {"b", "e"}
        assert len(pair) == 6       # 3 requests x (b, e)
    # the request's one record carries what the separate submit
    # instant did; the exporter writes the same phase names as before
    assert set(by_phase) == {"serving.request",
                             "serving.request/queue_wait",
                             "serving.request/prefill",
                             "serving.request/decode_steps"}
    # ids: one async track per request, phases share their request's
    # id, and args carry rid + tenant + outcome
    ids = {e["id"] for e in reqs if e.get("ph") in ("b", "e")}
    assert len(ids) == 3
    lives = [e for e in by_phase["serving.request"]
             if e["ph"] == "b"]
    assert {e["args"]["tenant"] for e in lives} == {"t0", "t1"}
    assert all(e["args"]["outcome"] == "retired" for e in lives)
    assert all(e["args"]["tokens"] == 4 for e in lives)
    assert all(e["args"]["prompt"] == 4 for e in lives)
    # nesting: each request's inner phases sit inside its life span
    for life in lives:
        rid = life["id"]
        end = next(e for e in by_phase["serving.request"]
                   if e["ph"] == "e" and e["id"] == rid)
        for phase in ("serving.request/queue_wait",
                      "serving.request/prefill",
                      "serving.request/decode_steps"):
            inner = [e for e in by_phase[phase] if e["id"] == rid]
            assert inner, (phase, rid)
            assert all(life["ts"] <= e["ts"] <= end["ts"] + 1e-3
                       for e in inner)


def test_aborted_request_trace_carries_outcome(tiny, tmp_path):
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.resilience import faults

    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, default_max_new=8)
    gw.warmup(prompt_lens=(4,))
    path = str(tmp_path / "abort_trace.jsonl")
    obs.trace.enable(path)
    try:
        with faults.active("serving:error=RuntimeError:nth=2:max=1"):
            st = gw.submit(np.arange(4, dtype=np.int32) % 64)
            with pytest.raises(SequenceAborted):
                st.result(timeout=60)
    finally:
        obs.trace.disable()
    gw.shutdown()
    evs = obs.trace.read_trace(path)
    lives = [e for e in evs if e.get("name") == "serving.request"
             and e.get("ph") == "b"]
    assert len(lives) == 1
    assert lives[0]["args"]["outcome"].startswith("aborted:")
    assert lives[0]["args"]["tokens"] >= 1   # salvaged tokens counted


# =========================================================================
# KV-pager occupancy observability (ISSUE 14 satellite)
# =========================================================================

def test_kv_occupancy_and_per_tenant_reserved_gauges(tiny):
    from deeplearning4j_tpu.obs import metrics

    model, net = tiny
    sched = DecodeScheduler(model, net, max_slots=2, block=8,
                            max_context=32)
    usable = sched.pager.n_pages - 1
    assert metrics.SERVING_KV_OCCUPANCY.snapshot()[""] == 0.0

    class _T(_Req):
        def __init__(self, prompt, max_new, tenant):
            super().__init__(prompt, max_new)
            self.tenant = tenant

    a = _T(np.arange(4) % 64, 8, "alice")
    b = _T(np.arange(4) % 64, 8, "bob")
    assert sched.admit(a) and sched.admit(b)
    occ = metrics.SERVING_KV_OCCUPANCY.snapshot()[""]
    used = usable - sched.pager.free_pages()
    assert occ == pytest.approx(used / usable)
    reserved = sched.pager.reserved_by_tenant()
    assert set(reserved) == {"alice", "bob"}
    assert reserved["alice"] == len(sched.pager.owned(a))
    fams = metrics.parse_exposition(metrics.exposition())
    assert fams[("dl4j_tpu_serving_kv_pages_reserved",
                 (("tenant", "alice"),))] == reserved["alice"]
    # release returns the gauges to empty
    sched.evict(a)
    sched.evict(b)
    assert metrics.SERVING_KV_OCCUPANCY.snapshot()[""] == 0.0
    assert sched.pager.reserved_by_tenant() == {}
    fams = metrics.parse_exposition(metrics.exposition())
    assert fams[("dl4j_tpu_serving_kv_pages_reserved",
                 (("tenant", "alice"),))] == 0.0


def test_pager_tenant_label_cardinality_capped():
    pager = KVPager(n_layers=1, n_kv_heads=1, head_dim=4,
                    n_pages=200, block=8, cache_quant=None)
    pager.max_tenant_labels = 3

    class _O:
        def __init__(self, tenant):
            self.tenant = tenant

    owners = [_O(f"tenant{i}") for i in range(6)]
    for o in owners:
        assert pager.alloc(1, o) is not None
    reserved = pager.reserved_by_tenant()
    assert set(reserved) == {"tenant0", "tenant1", "tenant2", "other"}
    assert reserved["other"] == 3
    for o in owners:
        pager.release(o)
    assert pager.reserved_by_tenant() == {}
    pager.check_invariants()


# =========================================================================
# ISSUE 16: speculative multi-token decode + copy-on-write prefix
# sharing — identity fences, refcount churn, zero-retrace grid
# =========================================================================

# the int8 halves of the two GPTNano fences below ride the slow lane:
# each costs ~15s of fresh-model compiles and tier-1 has an 870s
# wall-clock budget (the PR 10 flash-sweep precedent); the float
# halves stay tier-1 and the int8 shared-page roundtrip keeps a
# tier-1 fence via test_int8_pages_roundtrip_token_for_token
@pytest.mark.parametrize("cache_quant", [
    None, pytest.param("int8", marks=pytest.mark.slow)])
def test_spec_decode_token_identical_to_dense(cache_quant):
    """THE spec-decode fence: greedy speculative decode through the
    gateway (k=4, prompt-lookup drafts) emits exactly the dense
    ``generate()`` tokens — a wrong draft may only cost speed, never
    change an output."""
    model = GPTNano(vocab_size=64, max_len=64, seed=7,
                    cache_quant=cache_quant)
    net = model.init()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, t).astype(np.int32)
               for t in (5, 17, 9, 30, 3, 22)]
    budgets = [10, 4, 16, 8, 12, 6]
    dense = [np.asarray(model.generate(net, p[None], n_new=n))[0]
             for p, n in zip(prompts, budgets)]
    gw = ServingGateway(model, net, max_slots=3, block=8,
                        max_context=64, spec_k=4)
    # exactly the reachable buckets — warming 1/2 as well would buy
    # nothing but ~2 extra fresh-model compiles
    gw.warmup(prompt_lens=(3, 5, 9, 17, 22, 30))
    streams = [gw.submit(p, max_new=n)
               for p, n in zip(prompts, budgets)]
    for st, d in zip(streams, dense):
        np.testing.assert_array_equal(st.result(timeout=120), d)
    gw._sched.pager.check_invariants()
    assert gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1
    gw.shutdown()


@pytest.mark.parametrize("cache_quant", [
    None, pytest.param("int8", marks=pytest.mark.slow)])
def test_prefix_sharing_token_identical_to_dense(cache_quant):
    """Sharing fence (int8 case doubles as the shared-page roundtrip
    satellite): a whole-prompt sibling (tail CoW) and a
    novel-suffix sharer both ride the donor's pages yet reproduce
    dense ``generate()`` token-for-token, and every shared page
    returns to the free list afterwards."""
    from deeplearning4j_tpu.obs import metrics
    rng = np.random.default_rng(1)
    base = rng.integers(0, 64, 30).astype(np.int32)
    prompts = [base.copy(),          # donor
               base.copy(),          # tail share: whole prompt equal
               np.concatenate([base[:16], rng.integers(
                   0, 64, 8).astype(np.int32)])]   # full-page share
    budgets = [12, 12, 12]
    model = GPTNano(vocab_size=64, max_len=64, seed=7,
                    cache_quant=cache_quant)
    net = model.init()
    dense = [np.asarray(model.generate(net, p[None], n_new=n))[0]
             for p, n in zip(prompts, budgets)]
    gw = ServingGateway(model, net, max_slots=4, block=8,
                        max_context=64, prefix_sharing=True, spec_k=4)
    # one full-admit bucket reaches every prompt here (30/30/24 all
    # bucket to 32) and the suffix warmup closes downward on its own;
    # warming more admit buckets is pure compile time
    gw.warmup(prompt_lens=(30,))
    h0 = metrics.SERVING_PREFIX_HITS.snapshot()[""]
    s0 = metrics.SERVING_PREFIX_SAVED.snapshot()[""]
    streams = [gw.submit(p, max_new=n)
               for p, n in zip(prompts, budgets)]
    outs = [np.asarray(st.result(timeout=120)) for st in streams]
    for got, d in zip(outs, dense):
        np.testing.assert_array_equal(got, d)
    # both sharers hit the donor's chain and skipped prefix prefill
    assert metrics.SERVING_PREFIX_HITS.snapshot()[""] - h0 == 2
    assert metrics.SERVING_PREFIX_SAVED.snapshot()[""] - s0 >= 16 + 29
    gw._sched.pager.check_invariants()
    assert gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1
    gw.shutdown()


def test_spec_and_sharing_zero_retraces_after_warmup(tiny):
    """Any admission order over the warmed (k, bucket) grid — fresh
    prompts, exact repeats (tail CoW), shared prefixes with novel
    suffixes — stays retrace-free under the strict sentry."""
    from deeplearning4j_tpu.perf import sentry
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=3, block=8,
                        max_context=32, default_max_new=6,
                        spec_k=2, prefix_sharing=True)
    gw.warmup(prompt_lens=range(1, 25))
    before = sentry.total_traces()
    rng = np.random.default_rng(1)
    base = rng.integers(0, 64, 24).astype(np.int32)
    with sentry.strict():
        streams = [gw.submit(rng.integers(0, 64, int(t)), max_new=6)
                   for t in rng.integers(1, 25, 6)]
        streams.append(gw.submit(base, max_new=6))
        streams.append(gw.submit(base, max_new=6))
        streams.append(gw.submit(
            np.concatenate([base[:16],
                            rng.integers(0, 64, 4).astype(np.int32)]),
            max_new=6))
        for st in streams:
            st.result(timeout=120)
    assert sentry.total_traces() == before, \
        "spec/sharing traffic retraced after warmup"
    gw._sched.pager.check_invariants()
    gw.shutdown()


def test_spec_accept_metrics_exported(tiny):
    from deeplearning4j_tpu.obs import metrics
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=2, block=8,
                        max_context=32, spec_k=4)
    gw.warmup(prompt_lens=(4, 8))
    d0 = metrics.SERVING_SPEC_DRAFTED.snapshot()[""]
    a0 = metrics.SERVING_SPEC_ACCEPT.snapshot()[""]["count"]
    st = gw.submit(np.arange(6, dtype=np.int32) % 64, max_new=12)
    st.result(timeout=120)
    drafted = metrics.SERVING_SPEC_DRAFTED.snapshot()[""] - d0
    assert drafted > 0 and drafted % 3 == 0      # k-1 per spec step
    assert metrics.SERVING_SPEC_ACCEPT.snapshot()[""]["count"] > a0
    accepted = metrics.SERVING_SPEC_ACCEPTED.snapshot()[""]
    assert 0 <= accepted <= metrics.SERVING_SPEC_DRAFTED.snapshot()[""]
    gw.shutdown()


def test_pager_refcount_churn():
    """Seeded 120-op churn over alloc/adopt/cow/drop_ref/release with
    the invariant fence after EVERY transition: no page frees while a
    sibling still references it, refcounts conserve against the table,
    and the pool returns to full conservation at the end."""
    rng = np.random.default_rng(42)
    pager = KVPager(n_layers=1, n_kv_heads=1, head_dim=4, n_pages=33,
                    block=8, cache_quant=None)
    owners = {}          # name -> (owner object, exclusive pages)
    nxt = [0]

    def fresh():
        nxt[0] += 1
        return f"o{nxt[0]}"

    for _ in range(120):
        op = rng.choice(["alloc", "adopt", "cow", "drop", "release"])
        if op == "alloc":
            o = object()
            pages = pager.alloc(int(rng.integers(1, 4)), o)
            if pages is not None:
                owners[fresh()] = o
        elif op == "adopt" and owners:
            donor = owners[str(rng.choice(sorted(owners)))]
            pages = pager.owned(donor)
            if pages:
                share = pages[:int(rng.integers(1, len(pages) + 1))]
                taker = object()
                rc_before = {p: pager.refcount(p) for p in share}
                pager.adopt(share, taker)
                for p in share:
                    assert pager.refcount(p) == rc_before[p] + 1
                owners[fresh()] = taker
        elif op == "cow" and owners:
            o = owners[str(rng.choice(sorted(owners)))]
            shared = [p for p in pager.owned(o)
                      if pager.refcount(p) > 1]
            if shared and pager.free_pages():
                old = shared[0]
                rc = pager.refcount(old)
                new = pager.cow(o, old)
                assert new != old and pager.refcount(new) == 1
                # the original survived for its other holders
                assert pager.refcount(old) == rc - 1 >= 1
        elif op == "drop" and owners:
            o = owners[str(rng.choice(sorted(owners)))]
            pages = pager.owned(o)
            if pages:
                p = pages[int(rng.integers(len(pages)))]
                rc = pager.refcount(p)
                freed = pager.drop_ref(o, p)
                assert freed == (rc == 1)
        elif op == "release" and owners:
            name = str(rng.choice(sorted(owners)))
            pager.release(owners.pop(name))
        pager.check_invariants()
    for o in owners.values():
        pager.release(o)
    pager.check_invariants()
    assert pager.free_pages() == pager.n_pages - 1


def test_pager_chain_index_dies_with_pages():
    """A freed page invalidates every chain entry it belonged to —
    match_prefix can never hand out dead pages."""
    pager = KVPager(n_layers=1, n_kv_heads=1, head_dim=4, n_pages=9,
                    block=8, cache_quant=None)
    toks = np.arange(20, dtype=np.int32)
    a = object()
    pages = pager.alloc(3, a)
    pager.register_chain(toks, pages)
    m = pager.match_prefix(toks)
    assert m is not None and m[0] == 19 and m[2] is True
    assert pager.match_prefix(toks[:17])[0] == 16
    b = object()
    pager.adopt(pages[:2], b)       # sibling keeps first two alive
    pager.release(a)                # donor goes away; page 3 frees
    pager.check_invariants()
    # tail entry died with page 3 — the walk falls back to the
    # longest FULL-PAGE prefix the sibling's refs kept alive
    m = pager.match_prefix(toks)
    assert m is not None and m[0] == 16 and m[2] is False
    m = pager.match_prefix(toks[:17])
    assert m is not None and m[0] == 16              # prefix survives
    pager.release(b)
    pager.check_invariants()
    assert pager.match_prefix(toks[:17]) is None
    assert pager.free_pages() == pager.n_pages - 1


def test_cow_isolation_against_sibling():
    """CoW bookkeeping isolation: after a writer CoWs a shared page,
    the sibling still holds the original physical page (same id), so
    the writer's subsequent writes cannot touch the sibling's data."""
    pager = KVPager(n_layers=1, n_kv_heads=1, head_dim=4, n_pages=9,
                    block=8, cache_quant=None)
    a, b = object(), object()
    pa = pager.alloc(2, a)
    pager.adopt(pa, b)
    new = pager.cow(b, pa[1])
    assert new not in pa
    assert pager.owned(a) == pa                  # untouched
    assert set(pager.owned(b)) == {pa[0], new}
    assert pager.refcount(pa[1]) == 1            # back to exclusive
    pager.check_invariants()
    pager.release(a)
    pager.release(b)
    assert pager.free_pages() == pager.n_pages - 1


# =========================================================================
# a retention model behind the same scheduler: one state page a sequence
# =========================================================================

def _retention_model(**kw):
    return CausalTransformerLM(vocab_size=64, hidden=64, n_layers=2,
                               n_heads=4, n_kv_heads=2,
                               max_len=kw.pop("max_len", 128),
                               seed=kw.pop("seed", 3),
                               mixer="power_retention", **kw)


@pytest.fixture(scope="module")
def retention():
    model = _retention_model()
    return model, model.init()


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["fallback", "kernel"])
def test_retention_paged_decode_token_identical_to_dense(
        retention, monkeypatch, kernel):
    """Chunked prefill into state pages, then the in-place decode step:
    token for token what dense ``generate()`` returns, for prompts of
    less than a chunk, a chunk exactly and several chunks, with the
    kernel in the step (forced, interpret mode; a 128-wide head) or the
    fallback."""
    from deeplearning4j_tpu.serving import kv_pager as pager_mod
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", 16)
    if kernel:
        monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "1")
        model = CausalTransformerLM(
            vocab_size=64, hidden=256, n_layers=1, n_heads=2,
            n_kv_heads=1, max_len=64, seed=4, mixer="power_retention")
        net = model.init()
        lens, n_new = (5, 16, 21), 5
    else:
        model, net = retention
        lens, n_new = (5, 16, 17, 40, 33, 7), 12
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, n).astype(np.int32) for n in lens]
    gw = ServingGateway(model, net, max_slots=3, block=16,
                        max_context=64 if kernel else 96)
    report = gw.warmup(prompt_lens=lens)
    assert report["buckets"] == [16]        # one program, no buckets
    streams = [gw.submit(p, max_new=n_new) for p in prompts]
    outs = [np.asarray(s.result(timeout=300)) for s in streams]
    gw.shutdown()
    for p, out in zip(prompts, outs):
        dense = np.asarray(model.generate(net, p[None], n_new))[0]
        np.testing.assert_array_equal(out, dense)


def test_retention_state_pages_conserved_under_churn(retention,
                                                     monkeypatch):
    """Admit / step / evict churn over a pool with fewer pages than
    slots: one page a sequence, every invariant after every
    transition, the whole free list back at the end."""
    from deeplearning4j_tpu.serving import kv_pager as pager_mod
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", 16)
    model, net = retention
    sched = DecodeScheduler(model, net, max_slots=4, block=16,
                            max_context=64, n_pages=4)
    assert sched.pager.pages_for(1) == sched.pager.pages_for(999) == 1
    sched.warmup()
    rng = np.random.default_rng(4)
    live, refused = [], 0
    for it in range(90):
        op = rng.integers(0, 3)
        if op == 0:
            r = _Req(rng.integers(0, 64, int(rng.integers(1, 40))),
                     int(rng.integers(1, 9)))
            if sched.can_admit(r.prompt.size, r.max_new):
                assert sched.admit(r)
                assert len(sched.pager.owned(r)) == (0 if r.done else 1)
                if not r.done:
                    live.append(r)
            else:
                refused += 1
        elif op == 1:
            sched.step()
        elif live:
            sched.evict(live.pop(int(rng.integers(0, len(live)))))
        live = [r for r in live if not r.done]
        sched.pager.check_invariants()
    assert refused                  # three usable pages for four slots
    while any(s is not None for s in sched._slots):
        sched.step()
        sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1


def test_retention_inactive_slot_state_is_untouched(retention,
                                                    monkeypatch):
    """A decode step reads and writes the live slots' pages only: the
    page of a sequence that has left, and every free page, come out of
    a step bit for bit as they went in."""
    from deeplearning4j_tpu.serving import kv_pager as pager_mod
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", 16)
    model, net = retention
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=64)
    stay, leave = _Req(np.arange(9), 20), _Req(np.arange(20), 20)
    assert sched.admit(leave) and sched.admit(stay)
    sched.step()
    left = sched.pager.owned(leave)[0]
    kept = sched.pager.owned(stay)[0]
    sched.evict(leave)
    before = [np.asarray(a) for a in sched.pager.pool]
    sched.step()
    sched.step()
    for a, b in zip(before, (np.asarray(a) for a in sched.pager.pool)):
        others = [p for p in range(1, a.shape[1]) if p != kept]
        np.testing.assert_array_equal(a[:, others], b[:, others])
        assert (a[:, kept] != b[:, kept]).any()
    assert left in others


@pytest.mark.parametrize("kw,named", [
    (dict(prefix_sharing=True), "prefix_sharing"),
    (dict(spec_k=2), "spec_k")])
def test_retention_refuses_sharing_and_speculation(retention, kw,
                                                   named):
    model, net = retention
    with pytest.raises(ValueError, match=named + ".*snapshots"):
        DecodeScheduler(model, net, max_slots=2, block=16,
                        max_context=64, **kw)


def test_retention_zero_retraces_after_warmup(retention):
    from deeplearning4j_tpu.perf import sentry
    model, net = retention
    gw = ServingGateway(model, net, max_slots=3, block=16,
                        max_context=64, default_max_new=6)
    gw.warmup()
    free = gw.stats()["free_pages"]
    before = sentry.total_traces()
    rng = np.random.default_rng(1)
    with sentry.strict():
        streams = [gw.submit(rng.integers(0, 64, int(t)), max_new=6)
                   for t in rng.integers(1, 50, 10)]
        for st in streams:
            st.result(timeout=120)
    assert sentry.total_traces() == before, \
        "retention traffic retraced after warmup"
    gw.shutdown()
    assert gw.stats()["free_pages"] == free     # the leak check's read


# =========================================================================
# one decode step in flight (ISSUE 29): step n+1 is launched before
# step n's tokens are read. The tokens, the pool outside every live
# reservation, and every stream's end are what they were when each
# step was read before the next launch.
# =========================================================================

def _ahead_plan(temperature):
    """Admissions into a running batch; a budget (A) and an ``eos_id``
    (C) that end in the same step; a budget (B) whose last write fills
    its page, so the position after it opens a page it never reserved;
    slots re-used the iteration after they were freed (D, E, then G);
    the last live sequence ending by ``eos_id`` with a row in flight
    that is then wholly discarded; an admission (F) after that."""
    rng = np.random.default_rng(11)

    def req(t0, max_new, stop_at=None):
        prompt = rng.integers(0, 64, t0).astype(np.int32)
        if stop_at is None:
            return _Req(prompt, max_new, temperature=temperature)
        return _EosReq(prompt, max_new, stop_at,
                       temperature=temperature)

    a, c = req(5, 5), req(9, 20, stop_at=5)     # both end in step 3
    b = req(11, 6)                  # writes 11..15; 16 opens a page
    d, e = req(3, 7), req(17, 4)    # into A's and C's slots
    g = req(6, 20, stop_at=6)       # into E's slot; outlives D
    f = req(6, 5)
    plan = {0: [a, c], 2: [b], 4: [d, e], 7: [g], 15: [f]}
    return plan, [a, b, c, d, e, f, g]


@pytest.mark.parametrize("sample", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("mixer", ["softmax", "power_retention"])
def test_step_in_flight_serves_the_tokens_of_the_drained_order(
        tiny, retention, monkeypatch, mixer, sample):
    """Request by request, the scheduler with a step in flight serves
    what it serves when every step is read before the next launch
    (same launches in the same order, so the same draws under
    sampling), and under greedy decoding what dense ``generate()``
    returns."""
    from deeplearning4j_tpu.serving import kv_pager as pager_mod
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", 16)
    model, net = tiny if mixer == "softmax" else retention
    kw = dict(max_slots=3, block=8 if mixer == "softmax" else 16,
              max_context=64)
    if sample:
        kw.update(sample=True, top_k=16, seed=5)
    served = {}
    for drained in (True, False):
        sched = DecodeScheduler(model, net, **kw)
        plan, reqs = _ahead_plan(0.9 if sample else None)
        _drive(sched, plan, 16, drained)
        assert all(r.done and r.error is None for r in reqs)
        served[drained] = [r.tokens for r in reqs]
    assert served[False] == served[True]
    for r, toks in zip(reqs, served[False]):
        want = getattr(r, "stop_at", r.max_new)
        assert len(toks) == want
        if not sample:
            dense = np.asarray(model.generate(
                net, r.prompt[None], n_new=want))[0]
            np.testing.assert_array_equal(toks, dense[r.prompt.size:])
    assert sched.tokens_out == sum(map(len, served[False]))


@pytest.mark.parametrize("ends_by", ["eos", "budget"])
def test_row_launched_ahead_writes_inside_its_reservation(tiny, ends_by):
    """The step launched ahead of a sequence's last read: a sequence
    that ends by ``eos_id`` has a row in it, which writes one position
    inside the pages it still holds and nothing else; one that ends
    by budget, its last write filling a page, has none. The trash
    page with every slot live, the neighbour's other positions and
    every free page come out bit for bit as they went in; the freed
    pages then serve another sequence."""
    from deeplearning4j_tpu import obs
    model, net = tiny
    sched = DecodeScheduler(model, net, max_slots=2, block=8,
                            max_context=64)
    mark = obs.now()
    rng = np.random.default_rng(2)
    # four tokens either way: one at admission, three by steps; the
    # budget's three writes (13, 14, 15) fill its second page
    x = (_EosReq(rng.integers(0, 64, 11), 20, stop_at=4)
         if ends_by == "eos" else _Req(rng.integers(0, 64, 13), 4))
    nb = _Req(rng.integers(0, 64, 5), 20)
    assert sched.admit(x) and sched.admit(nb)
    x_pages, nb_pages = sched.pager.owned(x), sched.pager.owned(nb)
    for _ in range(3):
        sched.step()                # the third of them in flight
    assert len(x.tokens) == 3 and not x.done
    (before,) = (np.asarray(a) for a in sched.pager.pool)
    sched.step()                    # launches the fourth, reads the third
    assert x.done and len(x.tokens) == 4 and len(nb.tokens) == 4
    assert sched._inflight is not None and not sched.pager.owned(x)
    # a budget is known at launch, an ``eos_id`` only after the read
    rec = [r for r in obs.trace.records(since=mark)
           if r.name == "serving.decode_step"][-1]
    assert rec.counts["active"] == (2 if ends_by == "eos" else 1)
    (after,) = (np.asarray(a) for a in sched.pager.pool)
    changed = {int(p) for p in np.nonzero(
        (before != after).any(axis=(0, 2, 3, 4)))[0]}
    # the neighbour wrote position 5 + 3, the first of its second page
    wrote = {nb_pages[1]: 0}
    if ends_by == "eos":
        wrote[x_pages[1]] = 6       # position 11 + 3 of pages 8..15
    else:
        changed.discard(0)          # a slot masked out writes trash
    assert changed == set(wrote)
    for page, off in wrote.items():
        rest = np.arange(8) != off
        np.testing.assert_array_equal(before[:, page][:, rest],
                                      after[:, page][:, rest])
    # the freed pages go to the next admission, whose prefill follows
    # the stray write on the device
    y = _Req(rng.integers(0, 64, 13), 6)
    assert sched.admit(y)
    assert set(sched.pager.owned(y)) & set(x_pages)
    while sched.active_count() or sched._inflight is not None:
        sched.step()
    for r, n in ((y, 6), (nb, 20), (x, 4)):
        dense = np.asarray(model.generate(net, r.prompt[None], n_new=n))
        np.testing.assert_array_equal(r.tokens, dense[0, r.prompt.size:])
    sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1


def test_ahead_count_and_counter_follow_the_pipeline(tiny):
    """``ahead`` is 0 on the step that enters an empty pipeline (the
    first, and the first after a drain or an admission) and 1 on every
    other; the counter's growth over the step histogram's is that
    share; ``kv_pages`` counts the position each step writes although
    the mirror is a step behind."""
    from deeplearning4j_tpu import obs
    model, net = tiny
    sched = DecodeScheduler(model, net, max_slots=3, block=8,
                            max_context=64)
    mark = obs.now()
    ahead0 = obs.metrics.SERVING_AHEAD.snapshot()[""]
    steps0 = obs.metrics.SERVING_STEP.snapshot()[""]["count"]
    assert sched.admit(_Req(np.arange(7), 30))
    assert sched.admit(_Req(np.arange(14), 30))
    for _ in range(5):
        sched.step()
    sched.drain()
    for _ in range(3):
        sched.step()
    assert sched.admit(_Req(np.arange(3), 30))      # drains by itself
    for _ in range(2):
        sched.step()
    sched.drain()
    recs = [r for r in obs.trace.records(since=mark)
            if r.name == "serving.decode_step"]
    assert [r.counts["ahead"] for r in recs] \
        == [0, 1, 1, 1, 1, 0, 1, 1, 0, 1]
    assert [r.counts["active"] for r in recs] == [2] * 8 + [3] * 2
    want = [(7 + j) // 8 + 1 + (14 + j) // 8 + 1 for j in range(8)]
    want += [(7 + j) // 8 + (14 + j) // 8 + (3 + j - 8) // 8 + 3
             for j in (8, 9)]
    assert [r.counts["kv_pages"] for r in recs] == want
    drains = [r for r in obs.trace.records(since=mark)
              if r.name == "serving.drain"]
    assert [r.counts["tokens"] for r in drains] == [2, 2, 3]
    assert obs.metrics.SERVING_AHEAD.snapshot()[""] - ahead0 == 7
    assert (obs.metrics.SERVING_STEP.snapshot()[""]["count"] - steps0
            == sched.steps == 10)
    assert sched.tokens_out == 3 + 8 * 2 + 2 * 3


@pytest.mark.parametrize("kw", [dict(spec_k=2),
                                dict(prefix_sharing=True)],
                         ids=["spec_k2", "prefix_sharing"])
def test_modes_that_read_each_step_keep_no_step_in_flight(tiny, kw):
    """``spec_k > 1`` drafts from the tokens it has just read and
    copy-on-write reads the mirror's lengths: both read every step
    before the next launch, as before, and serve ``generate()``'s
    tokens."""
    from deeplearning4j_tpu import obs
    model, net = tiny
    sched = DecodeScheduler(model, net, max_slots=2, block=8,
                            max_context=64, **kw)
    mark = obs.now()
    rng = np.random.default_rng(6)
    reqs = [_Req(rng.integers(0, 64, t), n) for t, n in ((9, 8), (4, 5))]
    for r in reqs:
        assert sched.admit(r)
    n = 0
    while sched.active_count():
        n += 1
        assert sched.step() >= 1 and sched._inflight is None
        assert sum(len(r.tokens) for r in reqs) >= n + 2
    for r in reqs:
        dense = np.asarray(model.generate(net, r.prompt[None],
                                          n_new=r.max_new))[0]
        np.testing.assert_array_equal(r.tokens, dense[r.prompt.size:])
    steps = [r for r in obs.trace.records(since=mark)
             if r.name in ("serving.decode_step", "serving.spec_step")]
    assert len(steps) == n
    assert not any(r.counts.get("ahead") for r in steps)


def _handdriven_gateway(model, net, n_reqs=2, max_new=12):
    """A gateway without its worker thread, its iterations made by the
    test: after two of them one step has been read and one is in
    flight."""
    gw = ServingGateway(model, net, max_slots=3, block=8,
                        max_context=64, start=False)
    rng = np.random.default_rng(8)
    streams = [gw.submit(rng.integers(0, 64, 5 + 3 * i).astype(np.int32),
                         max_new=max_new) for i in range(n_reqs)]
    gw._iterate(1)
    gw._iterate(2)
    assert gw._sched._inflight is not None
    assert [s.n_generated() for s in streams] == [2] * n_reqs
    return gw, streams


def _pool_whole(gw):
    gw._sched.pager.check_invariants()
    return gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1


def test_cancel_with_a_step_in_flight_discards_its_row(tiny):
    model, net = tiny
    gw, (gone, stays) = _handdriven_gateway(model, net)
    assert gw.cancel(gone)
    gw._iterate(3)      # evicts, launches for one slot, reads for two
    assert gone.done() and gone.error() is None
    assert gone.n_generated() == 2 and stays.n_generated() == 3
    it = 3
    while not stays.done():
        it += 1
        gw._iterate(it)
    gw._iterate(it + 1)             # nothing live: drains, then parks
    assert gone.n_generated() == 2 and gw._sched._inflight is None
    dense = np.asarray(model.generate(net, stays.prompt[None], n_new=12))
    np.testing.assert_array_equal(stays.result(timeout=1), dense[0])
    assert _pool_whole(gw)
    # the last sequence cancelled with its row in flight: the worker
    # reads that step off before it parks
    last = gw.submit(np.arange(6, dtype=np.int32), max_new=12)
    for it in range(20, 23):
        gw._iterate(it)
    assert gw.cancel(last)
    gw._iterate(23)
    assert last.done() and last.n_generated() == 3
    assert gw._sched._inflight is None and _pool_whole(gw)
    gw.shutdown(timeout=1)


@pytest.mark.parametrize("where", ["inject", "read"])
def test_fault_with_a_step_in_flight_sheds_every_stream_once(tiny, where):
    """A fault raised at the ``serving`` site, or by the read of the
    step in flight (a device error surfaces one iteration after its
    launch, with its successor already launched): every stream fails
    with the tokens it had, the steps in flight deliver nothing more,
    no page leaks and the gateway serves on."""
    from deeplearning4j_tpu.resilience import faults
    model, net = tiny
    gw, streams = _handdriven_gateway(model, net, n_reqs=3)
    if where == "inject":
        with faults.active("serving:error=RuntimeError:nth=1:max=1"):
            gw._iterate(3)
    else:
        class Lost:
            def __array__(self, *a, **kw):
                raise RuntimeError("device lost")
        gw._sched._inflight.nxt = Lost()
        gw._iterate(3)
    assert gw._sched._inflight is None and _pool_whole(gw)
    for st in streams:
        assert st.done() and st.n_generated() == 2
        with pytest.raises(SequenceAborted) as err:
            st.result(timeout=1)
        assert err.value.tokens == st._tokens
    post = gw.submit(np.arange(7, dtype=np.int32), max_new=5)
    for it in range(4, 12):
        gw._iterate(it)
    assert [st.n_generated() for st in streams] == [2] * 3
    dense = np.asarray(model.generate(net, post.prompt[None], n_new=5))
    np.testing.assert_array_equal(post.result(timeout=1), dense[0])
    assert _pool_whole(gw)
    gw.shutdown(timeout=1)


def test_pause_drains_the_step_in_flight_and_resume_runs_on(tiny):
    model, net = tiny
    gw, streams = _handdriven_gateway(model, net)
    gw._pause.set()
    gw._iterate(3)
    assert gw._parked.is_set() and gw._sched._inflight is None
    assert [s.n_generated() for s in streams] == [3, 3]
    gw._iterate(4)                  # held: nothing is launched
    assert [s.n_generated() for s in streams] == [3, 3]
    assert gw._sched._inflight is None
    gw.resume()
    it = 4
    while not all(s.done() for s in streams):
        it += 1
        gw._iterate(it)
    for st in streams:
        dense = np.asarray(model.generate(net, st.prompt[None], n_new=12))
        np.testing.assert_array_equal(st.result(timeout=1), dense[0])
    assert _pool_whole(gw)
    gw.shutdown(timeout=1)


@pytest.mark.parametrize("drain", [True, False])
def test_shutdown_with_a_step_in_flight_ends_every_stream(tiny, drain):
    """The worker's own loop: ``shutdown(drain=True)`` serves every
    live sequence to its end (``generate()``'s tokens),
    ``drain=False`` sheds them; either way no stream gets a token
    after its end, none is left open, no page leaks."""
    model, net = tiny
    gw = ServingGateway(model, net, max_slots=3, block=8,
                        max_context=64)
    gw.warmup(prompt_lens=(5, 8, 11))
    rng = np.random.default_rng(9)
    streams = [gw.submit(rng.integers(0, 64, 5 + 3 * i).astype(np.int32),
                         max_new=40) for i in range(3)]
    for _ in range(2000):
        if all(s.n_generated() >= 3 for s in streams):
            break
        time.sleep(0.002)
    gw.shutdown(drain=drain, timeout=60)
    assert all(s.done() for s in streams)
    ended = [s.n_generated() for s in streams]
    assert gw._sched._inflight is None and _pool_whole(gw)
    for st in streams:
        dense = np.asarray(model.generate(net, st.prompt[None],
                                          n_new=40))[0]
        if drain:
            np.testing.assert_array_equal(st.result(timeout=1), dense)
        elif st.error() is not None:
            assert isinstance(st.error(), ServingShutdownError)
        got = np.asarray(st._tokens)
        np.testing.assert_array_equal(
            got, dense[st.prompt.size:st.prompt.size + got.size])
    time.sleep(0.05)
    assert [s.n_generated() for s in streams] == ended

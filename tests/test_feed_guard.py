"""The host-side guard of the decode step's feed
(``DecodeScheduler._check_feed``).

The page walks' kernels (``paged_decode_attention``,
``latent_decode_attention``) issue their copies with the compiler's
bounds checks off, so a page number out of the pool or a length past
a slot's row has to fail HERE, on the host, where the scheduler
rebuilds the feed from its mirror, before any device call. The guard
has no switch: every scheduler test runs under it, and the churn at
the end holds that sound traffic never trips it.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.ops import latent as L
from deeplearning4j_tpu.ops import moe as M
from deeplearning4j_tpu.serving import DecodeScheduler
from deeplearning4j_tpu.serving.kv_pager import PageTableError
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM
from test_serving import _Req

BLOCK, CONTEXT, SLOTS = 8, 64, 3


def _kv():
    return CausalTransformerLM(vocab_size=64, hidden=32, n_layers=2,
                               n_heads=2, n_kv_heads=1, max_len=CONTEXT,
                               seed=9)


def _window():
    return CausalTransformerLM(
        vocab_size=64, hidden=32, n_layers=2, n_heads=2, n_kv_heads=1,
        head_dim=16, max_len=CONTEXT, rope_theta=1.5e6, window=16,
        window_layers=[1], rope_layers=[1], seed=11,
        updater=upd.Sgd(learning_rate=0.0))


def _latent():
    return CausalTransformerLM(
        vocab_size=64, hidden=32, n_layers=2, n_heads=2, max_len=CONTEXT,
        ffn_mult=2.0, mixer="latent",
        latent=L.LatentSpec(q_rank=24, kv_rank=16, nope=8, rope=8, v=8),
        experts=M.ExpertSpec(width=16, n_held=2, n_routed=4, top_k=2,
                             n_group=1, topk_group=1, scale=1.0,
                             n_shared=1, offset=0, first_dense=1),
        updater=upd.Sgd(0.0), seed=3)


_KINDS = {"kv": _kv, "window": _window, "latent": _latent}


@pytest.fixture(scope="module", params=sorted(_KINDS))
def served(request):
    model = _KINDS[request.param]()
    return request.param, model, model.init()


def _sched(model, net):
    return DecodeScheduler(model, net, max_slots=SLOTS, block=BLOCK,
                           max_context=CONTEXT)


def _entry_at_p(s):
    s._page_table[0, 1] = s.pager.n_pages


def _negative_entry(s):
    s._page_table[0, 0] = -1


def _entry_of_a_free_slots_row(s):      # no slot there: still the step's
    s._page_table[SLOTS - 1, -1] = s.pager.n_pages + 7


def _length_past_the_row(s):
    s._lengths[0] = CONTEXT + 1


def _budget_past_the_row(s):
    s._slots[0].remaining = CONTEXT


_FAULTS = [_entry_at_p, _negative_entry, _entry_of_a_free_slots_row,
           _length_past_the_row, _budget_past_the_row]


@pytest.mark.parametrize("fault", _FAULTS, ids=lambda f: f.__name__[1:])
def test_a_bad_table_raises_on_the_host_before_any_device_call(
        served, fault):
    kind, model, net = served
    sched = _sched(model, net)
    assert sched.admit(_Req(np.arange(1, 12), 9))
    sched.step()
    sched.drain()           # the mirror level with the device
    sched._check_feed()     # sound so far
    launched = []
    real = sched._step_fn
    sched._step_fn = lambda *a: launched.append(1) or real(*a)
    fault(sched)
    sched._feed_dirty = True        # as an admission leaves it
    with pytest.raises(PageTableError):
        sched.step()
    assert not launched


def test_a_ring_too_short_for_its_window_raises_on_the_host():
    """A window layer's table is the slot's ring, built inside the
    step from the window pool's SHAPE (so its entries are the pool's
    own): what the host holds is that a ring is long enough that a
    walk of ``window`` positions wraps once at most."""
    model = _window()
    sched = _sched(model, model.init())
    assert sched.admit(_Req(np.arange(1, 30), 9))
    sched._check_feed()
    full, ring = sched.pager.pool
    assert ring.shape[1] == 1 + SLOTS * sched.pager.ring
    for pool in (ring[:, :-1],                      # a ring cut short
                 ring[:, :1 + SLOTS * 2]):          # rings of 2 < 3
        sched.pager.pool = (full, pool)
        sched._feed_dirty = True
        with pytest.raises(PageTableError, match="window pool"):
            sched.step()


def test_sound_churn_never_trips_the_guard(served):
    """Seeded random admissions, steps, retirements and evictions
    through ``KVPager``: the guard and the pager's own invariants hold
    after every transition, and nothing leaks."""
    kind, model, net = served
    sched = _sched(model, net)
    rng = np.random.default_rng(48)
    live, checked = [], [0]
    real = sched._check_feed

    def counted():
        checked[0] += 1
        real()
    sched._check_feed = counted
    for it in range(60):
        op = rng.integers(0, 3)
        if op == 0:
            r = _Req(rng.integers(0, 64, int(rng.integers(1, 40))),
                     int(rng.integers(1, 24)))
            if sched.can_admit(r.prompt.size, r.max_new):
                assert sched.admit(r)
                if not r.done:
                    live.append(r)
        elif op == 1:
            sched.step()
        elif live:
            sched.evict(live.pop(int(rng.integers(0, len(live)))))
        live = [r for r in live if not r.done]
        real()
        sched.pager.check_invariants()
    while any(s is not None for s in sched._slots):
        sched.step()
    real()
    sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1
    assert checked[0] > 5       # once a rebuilt feed, not once a step
    assert checked[0] < sched.steps

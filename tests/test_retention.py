"""Power retention (ops/retention.py, the ``mixer="power_retention"``
decoder): three forms of one function, the model's three forwards, and
the gateway's prefill-then-decode through the state pool held against
the benchmark's plain reference.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import retention as R
from deeplearning4j_tpu.serving import DecodeScheduler
from deeplearning4j_tpu.serving import kv_pager as pager_mod
from deeplearning4j_tpu.nn import decoder_infer as di
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _operands(seed, b=2, t=37, h=4, n_kv=2, d=16, bias=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, t, h, d)),
            jax.random.normal(ks[1], (b, t, n_kv, d)),
            jax.random.normal(ks[2], (b, t, n_kv, d)),
            R.log_gate(jax.random.normal(ks[3], (b, t, n_kv)) + bias))


@pytest.mark.parametrize("d", [8, 16, 32])
def test_phi_inner_product_is_the_squared_dot(d):
    ka, kb = jax.random.split(jax.random.PRNGKey(d))
    a, b = jax.random.normal(ka, (7, d)), jax.random.normal(kb, (7, d))
    got = jnp.sum(R.phi_read(a) * R.phi_write(b), axis=-1)
    want = jnp.sum(a * b, axis=-1) ** 2
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # the stored rows hold every logical product once (weight 1 or 2)
    # and the mirror images of the diagonal blocks at weight 0
    w2 = R._layout(d)[2]
    assert w2.shape == (R.state_rows(d),)
    assert int((w2 > 0).sum()) == R.logical_state_rows(d)


def test_state_rows_of_a_128_wide_key():
    assert R.logical_state_rows(128) == 8256
    assert R.state_rows(128) == 8704
    with pytest.raises(ValueError, match="multiple of 8"):
        R.state_rows(12)


@pytest.mark.parametrize("chunk", [1, 8, 16, 37, 64])
def test_chunked_form_equals_attention_form(chunk):
    q, k, v, lg = _operands(0)
    want = R.retention_attention(q, k, v, lg)
    got, _ = R.retention_sequence(q, k, v, lg, chunk)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_recurrent_form_equals_attention_form_and_chunked_state():
    q, k, v, lg = _operands(1)
    want = R.retention_attention(q, k, v, lg)
    state, ys = R.zero_state(2, 2, 16), []
    for t in range(q.shape[1]):
        y, state = R.retention_step(q[:, t], k[:, t], v[:, t], lg[:, t],
                                    state)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want, rtol=1e-4,
                               atol=5e-5)
    _, chunked = R.retention_sequence(q, k, v, lg, chunk=8)
    for a, b in zip(state, chunked):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("chunk", [8, 16])
def test_padded_rows_add_nothing_to_the_state(chunk):
    """Prompts that are no multiple of the chunk: the state after the
    padded run is the state after the last REAL row exactly."""
    q, k, v, lg = _operands(2)                  # 37 rows
    _, want = R.retention_sequence(q[:1], k[:1], v[:1], lg[:1], chunk)
    _, short = R.retention_sequence(q[1:, :20], k[1:, :20], v[1:, :20],
                                    lg[1:, :20], chunk)

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, 11)) + ((0, 0),) * (x.ndim - 2),
                       constant_values=3.0)     # junk, not zeros

    valid = jnp.arange(48)[None, :] < jnp.array([37, 20])[:, None]
    _, got = R.retention_sequence(pad(q), pad(k), pad(v), pad(lg), chunk,
                                  valid=valid)
    for g, w, s in zip(got, want, short):
        np.testing.assert_allclose(g[0], w[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(g[1], s[0], rtol=1e-5, atol=1e-5)


def _model(**kw):
    kw.setdefault("seed", 3)
    return CausalTransformerLM(vocab_size=64, hidden=64, n_layers=2,
                               n_heads=4, n_kv_heads=2,
                               max_len=kw.pop("max_len", 128),
                               mixer="power_retention", **kw)


@pytest.fixture(scope="module")
def retention_lm():
    model = _model()
    return model, model.init()


def test_mixer_argument_is_checked():
    with pytest.raises(ValueError, match="mixer"):
        CausalTransformerLM(mixer="linear")
    with pytest.raises(ValueError, match="cache_quant"):
        _model(cache_quant="int8")
    with pytest.raises(ValueError, match="sequence_parallel"):
        _model(sequence_parallel="ring")


def test_generate_equals_the_training_forward(retention_lm):
    """Dense ``generate()`` (padded prefill into one state a row, then
    the recurrence) picks at every position the training forward's
    (chunked form) best token."""
    model, net = retention_lm
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 64, (2, 11)).astype(np.int32)
    out = np.asarray(model.generate(net, prompt, 9))
    logits = np.asarray(net.output(out[:, :-1]))
    np.testing.assert_array_equal(logits.argmax(-1)[:, 10:], out[:, 11:])
    beam = np.asarray(model.generate_beam(net, prompt, 5, beams=1))
    np.testing.assert_array_equal(beam, out[:, :16])


def test_fit_trains_the_retention_model():
    model = _model(seed=5)
    net = model.init()
    rng = np.random.default_rng(1)
    x = rng.integers(0, 64, (4, 32)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    scores = []
    for _ in range(6):
        net.fit(x, y)
        scores.append(float(net.score()))
    assert np.isfinite(scores).all() and scores[-1] < 0.9 * scores[0]


def test_weights_already_in_the_compute_dtype_are_served_as_they_are():
    """No second copy (ROADMAP M6): a net whose float leaves have the
    compute dtype IS the decode tree; float32 masters still get their
    bf16 copy."""
    model = _model(compute_dtype="bfloat16")
    net = model.init()
    copy = model.decode_params(net)
    assert jax.tree.leaves(copy)[0] is not jax.tree.leaves(net.params)[0]
    net.params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                              net.params)
    served = model.decode_params(net)
    assert all(a is b for a, b in zip(jax.tree.leaves(served),
                                      jax.tree.leaves(net.params)))


# -- the gateway's path against the plain reference ----------------------

class _Req:
    def __init__(self, prompt, max_new):
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new, self.temperature, self.eos_id = max_new, None, None
        self.tokens, self.done = [], False

    def push(self, tok):
        self.tokens.append(int(tok))

    def finish(self):
        self.done = True

    def fail(self, e):
        raise e


def _own_records(mark):
    """The ring's records since ``mark`` that THIS thread made. The
    ring is the process's: a gateway that another test of the same
    worker left decoding on its own thread adds ``serving.decode_step``
    records of its own, and "the last one" was then not ours."""
    import threading

    from deeplearning4j_tpu import obs
    me = threading.get_ident()
    return [r for r in obs.trace.records(since=mark) if r.tid == me]


_TOY = dict(num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
            rms_norm_eps=1e-6,
            assumed={"power": 2, "normaliser_eps": R.RETENTION_EPS})


def _served_logits(model, net, seq, t0, monkeypatch, chunk=16,
                   state_dtype=None):
    """Teacher-forced logits of ``seq[t0 - 1:]`` by the gateway's own
    programs: the chunked prefill into the sequence's state page, then
    THE paged block a position at a time over the pool. With
    ``state_dtype`` the pool is rounded to it after every program."""
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", chunk)
    sched = DecodeScheduler(model, net, max_slots=3, block=16,
                            max_context=96)
    assert sched.prefill_chunk == chunk

    def rounded(pool):
        if state_dtype is None:
            return pool
        return tuple(a.astype(state_dtype).astype(a.dtype) for a in pool)

    # another sequence first, so that ours is not in slot 0 / page 1
    other = _Req(np.arange(5) % 64, 40)
    assert sched.admit(other)
    req = _Req(seq[:t0], len(seq) - t0 + 1)
    assert sched.admit(req)
    sched.pager.pool = rounded(sched.pager.pool)
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
    rows, first = [], req.tokens[0]
    for j, tok in enumerate(seq[t0:]):
        prev = np.zeros(3, np.int32)
        prev[slot] = tok
        lengths = np.zeros(3, np.int32)
        lengths[slot] = t0 + j
        logits, pool = logits_step(
            params, sched.pager.pool, jnp.asarray(sched._page_table),
            jnp.asarray(lengths), jnp.asarray(active), jnp.asarray(prev))
        sched.pager.pool = rounded(pool)
        rows.append(np.asarray(logits[slot], np.float32))
    return first, np.stack(rows)


#: float32 on the CPU, logits up to 3 in size: prefill by chunks and
#: decode by the recurrence differ from the reference's one
#: attention-form pass only in the order of float32 sums (read: 2.4e-6
#: at most over the three prompts); a state rounded to bf16 moves a
#: logit by 0.018 to 0.040 and a dropped gate by 0.9 to 1.8
LOGIT_TOL = 5e-5


@pytest.mark.parametrize("t0", [16, 23, 41])
def test_prefill_then_paged_decode_matches_the_reference_logits(
        retention_lm, monkeypatch, t0):
    """Prompts of one chunk exactly, of one and a part, of two and a
    part: the state written at admission is the state after position
    ``t0 - 1``, and every decoded position's logits are the plain
    reference's."""
    from benchmarks.reference import retention_lm as ref
    model, net = retention_lm
    rng = np.random.default_rng(t0)
    seq = rng.integers(0, 64, t0 + 12).astype(np.int32)
    first, got = _served_logits(model, net, seq, t0, monkeypatch)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits_from(
            net.params, jnp.asarray(seq), t0 - 1, rows=13,
            **ref.dims(_TOY)))
    assert first == int(want[0].argmax())
    assert np.abs(got - want[1:]).max() < LOGIT_TOL
    # the tolerance is tight enough to tell: the reference without its
    # gate is another function, and so is the program with its state
    # held in bf16
    with jax.default_matmul_precision("highest"):
        ungated = np.asarray(ref.logits_from(
            net.params, jnp.asarray(seq), t0 - 1, rows=13,
            use_gate=False, **ref.dims(_TOY)))
    assert np.abs(got - ungated[1:]).max() > 1000 * LOGIT_TOL
    _, low = _served_logits(model, net, seq, t0, monkeypatch,
                            state_dtype=jnp.bfloat16)
    assert np.abs(low - want[1:]).max() > 100 * LOGIT_TOL


def test_records_count_state_bytes_and_chunks(retention_lm, monkeypatch):
    from deeplearning4j_tpu import obs
    model, net = retention_lm
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", 16)
    sched = DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=96)
    mark = obs.now()
    moved = obs.metrics.SERVING_STATE_MOVED.snapshot()[""]
    reqs = [_Req(np.arange(t) % 64, 4) for t in (40, 7)]
    for r in reqs:
        assert sched.admit(r)
    sched.step()
    recs = _own_records(mark)
    chunks = [r.counts["chunks"] for r in recs
              if r.name == "serving.prefill"]
    assert chunks == [3, 1]
    step = [r for r in recs if r.name == "serving.decode_step"][-1]
    # 2 slots x 2 layers x 2 kv heads x 136 rows x (16 + 1) x 4 bytes,
    # read and written
    per_slot = 2 * 4 * 2 * 2 * 136 * 17
    assert sched.pager.state_bytes_per_slot == per_slot
    assert step.counts["state_bytes"] == 2 * per_slot
    assert step.counts["kv_pages"] == 0
    assert (obs.metrics.SERVING_STATE_MOVED.snapshot()[""] - moved
            == 2 * per_slot)
    assert (obs.metrics.SERVING_STATE_POOL.snapshot()[""]
            == sched.pager.pool_bytes())


# -- one decode step in flight over the state pool (ISSUE 29) ------------

class _EosReq(_Req):
    """Ends by ``eos_id`` at its ``stop_at``-th token, whatever that
    is: the scheduler compares after the push, and cannot know before
    it has read the step."""

    def __init__(self, prompt, max_new, stop_at):
        super().__init__(prompt, max_new)
        self.stop_at = stop_at

    @property
    def eos_id(self):
        return (self.tokens[-1] if len(self.tokens) == self.stop_at
                else None)

    @eos_id.setter
    def eos_id(self, _):
        pass


@pytest.mark.parametrize("ends_by", ["eos", "budget"])
def test_row_launched_ahead_rewrites_its_own_state_page_only(
        retention_lm, monkeypatch, ends_by):
    """The step launched before a sequence's last tokens are read: a
    sequence that ends by ``eos_id`` has a row in it, which rewrites
    the one state page it still holds; one that ends by budget has
    none. The trash page with every slot live and the free pages come
    out bit for bit; the page, released and handed to another
    sequence, starts that one from an empty state."""
    from deeplearning4j_tpu import obs
    model, net = retention_lm
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", 16)
    sched = DecodeScheduler(model, net, max_slots=2, block=16,
                            max_context=96)
    mark = obs.now()
    rng = np.random.default_rng(4)
    x = (_EosReq(rng.integers(0, 64, 21), 20, stop_at=4)
         if ends_by == "eos" else _Req(rng.integers(0, 64, 21), 4))
    nb = _Req(rng.integers(0, 64, 7), 12)
    assert sched.admit(x) and sched.admit(nb)
    (x_page,), (nb_page,) = sched.pager.owned(x), sched.pager.owned(nb)
    for _ in range(3):
        sched.step()                # the third of them in flight
    assert len(x.tokens) == 3 and not x.done
    before = [np.asarray(a) for a in sched.pager.pool]
    sched.step()                    # launches the fourth, reads the third
    assert x.done and len(x.tokens) == 4 and len(nb.tokens) == 4
    assert sched._inflight is not None and not sched.pager.owned(x)
    rec = [r for r in _own_records(mark)
           if r.name == "serving.decode_step"][-1]
    live = 2 if ends_by == "eos" else 1
    assert rec.counts["ahead"] == 1 and rec.counts["active"] == live
    assert rec.counts["state_bytes"] == live * sched.pager.state_bytes_per_slot
    after = [np.asarray(a) for a in sched.pager.pool]
    changed = set()
    for a, b in zip(before, after):
        axes = tuple(i for i in range(a.ndim) if i != 1)
        changed |= {int(p) for p in np.nonzero((a != b).any(axis=axes))[0]}
    if ends_by == "budget":
        changed.discard(0)          # a slot masked out may write trash
    assert changed == ({nb_page, x_page} if ends_by == "eos"
                       else {nb_page})
    y = _Req(rng.integers(0, 64, 33), 6)
    assert sched.admit(y)
    assert sched.pager.owned(y) == [x_page]
    while sched.active_count() or sched._inflight is not None:
        sched.step()
    for r, n in ((y, 6), (nb, 12), (x, 4)):
        dense = np.asarray(model.generate(net, r.prompt[None], n))
        np.testing.assert_array_equal(r.tokens, dense[0, r.prompt.size:])
    sched.pager.check_invariants()
    assert sched.pager.free_pages() == sched.pager.n_pages - 1


def test_gateway_ends_every_stream_with_a_retention_step_in_flight(
        retention_lm, monkeypatch):
    """The gateway's iterations made by hand over the state pool: a
    cancel, a pause and a fault each meet a step in flight; no stream
    gets a token after its end, none stays open, every state page
    comes back."""
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.serving import SequenceAborted, ServingGateway
    model, net = retention_lm
    monkeypatch.setattr(pager_mod, "PREFILL_CHUNK", 16)
    gw = ServingGateway(model, net, max_slots=3, block=16,
                        max_context=96, start=False)
    rng = np.random.default_rng(5)
    gone, held, shed = (gw.submit(rng.integers(0, 64, t).astype(np.int32),
                                  max_new=12) for t in (5, 19, 33))
    gw._iterate(1)
    gw._iterate(2)
    assert gw._sched._inflight is not None
    assert gw.cancel(gone)
    gw._iterate(3)                  # the cancelled row is discarded
    assert gone.done() and gone.n_generated() == 2
    assert held.n_generated() == shed.n_generated() == 3
    gw._pause.set()
    gw._iterate(4)                  # the hold reads the step in flight
    assert gw._parked.is_set() and gw._sched._inflight is None
    assert held.n_generated() == shed.n_generated() == 4
    gw.resume()
    gw._iterate(5)
    with faults.active("serving:error=RuntimeError:nth=1:max=1"):
        gw._iterate(6)
    assert gw._sched._inflight is None
    for st in (held, shed):
        assert st.n_generated() == 4
        with pytest.raises(SequenceAborted):
            st.result(timeout=1)
    post = gw.submit(rng.integers(0, 64, 9).astype(np.int32), max_new=5)
    for it in range(7, 14):
        gw._iterate(it)
    dense = np.asarray(model.generate(net, post.prompt[None], 5))
    np.testing.assert_array_equal(post.result(timeout=1), dense[0])
    assert (gone.n_generated(), held.n_generated()) == (2, 4)
    gw._sched.pager.check_invariants()
    assert gw._sched.pager.free_pages() == gw._sched.pager.n_pages - 1
    gw.shutdown(timeout=1)

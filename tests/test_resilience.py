"""Resilience subsystem (ARCHITECTURE.md §10): deterministic fault
injection, hardened checkpoint pipeline, retry/preemption policy,
serving load-shedding.

Reference analog (SURVEY §5): the reference's recovery story was
CheckpointListener + ModelSerializer resume + Spark task retry, tested
only by real outages. Here failure itself is a managed artifact: every
test drives a REAL code path (fit loop, checkpoint IO, serving queue)
through a seeded fault plan and asserts recovery — including the
acceptance fences: injected-fault matrix with obs counters, zero-
overhead off path, crash-consistency under kill -9, SIGTERM-during-fit
clean preemption.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import zipfile
from pathlib import Path

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.obs import metrics
from deeplearning4j_tpu.resilience import checkpoint as rck
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.policy import (PreemptionHandler,
                                                  RetryPolicy, classify)
from deeplearning4j_tpu.serialization import ModelSerializer
from deeplearning4j_tpu.train.fault_tolerance import (
    FaultTolerantTrainer, newest_checkpoint, resume_or_init)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.reset()
    yield
    faults.reset()


def _mlp(seed=11, n_in=8, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Adam(learning_rate=5e-3)).list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _data(n=96, seed=5):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return DataSet(x, y)


def _iter(ds, bs=24):
    return ListDataSetIterator([b for b in ds.batch_by(bs)],
                               batch_size=bs)


def _params_equal(a, b, tol=1e-6):
    import jax
    return all(np.allclose(np.asarray(x), np.asarray(y), atol=tol)
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _counter(metric, **labels):
    return metric.labels(**labels).get() if labels \
        else metric._children[()].get()


# =========================================================================
# fault plan parsing + off-path contract
# =========================================================================

def test_plan_parse_roundtrip():
    p = faults.FaultPlan.parse(
        "ckpt_*:error=OSError:p=0.5:seed=3:max=2;step:nth=6")
    assert len(p.rules) == 2
    assert p.rules[0].error == "OSError" and p.rules[0].max_fires == 2
    assert p.rules[1].site == "step" and p.rules[1].nth == 6
    assert p.rules[0].matches("ckpt_write")
    assert p.rules[0].matches("ckpt_commit")
    assert not p.rules[0].matches("step")


def test_named_plans_all_parse():
    for name in faults.NAMED_PLANS:
        assert faults.FaultPlan.parse(name).rules


@pytest.mark.parametrize("bad", ["", "step:frequency=2", "step:error=Nope",
                                 "step:p", "ckptwrite:error=OSError"])
def test_plan_parse_rejects(bad):
    with pytest.raises(ValueError):
        faults.FaultPlan.parse(bad)


def test_seeded_probability_is_deterministic():
    fire_pattern = []
    for _ in range(2):
        r = faults.FaultRule("s", p=0.5, seed=7, max_fires=1 << 30)
        fire_pattern.append([r.should_fire() for _ in range(32)])
    assert fire_pattern[0] == fire_pattern[1]
    assert any(fire_pattern[0]) and not all(fire_pattern[0])


def test_off_path_zero_evaluations():
    """Acceptance: with no plan active, training + checkpoint IO +
    serving pass every fault site and the evaluation counter never
    moves — the sites cost one branch, nothing else executes."""
    assert faults.plan() is None
    before = faults.evaluations()
    net = _mlp()
    ds = _data(48)
    net.fit(_iter(ds), epochs=1)                      # step + iterator
    ModelSerializer.write_model(net, "/tmp/_faults_off_probe.zip")
    os.unlink("/tmp/_faults_off_probe.zip")           # ckpt sites
    assert faults.evaluations() == before == 0
    assert faults.stats() == {}
    # flip the gate on: the SAME paths now evaluate sites (a valid
    # site whose nth is astronomically far away never fires)
    with faults.active("step:nth=1000000000"):
        net.fit(_iter(ds), epochs=1)
    assert faults.evaluations() > 0


# =========================================================================
# hardened checkpoint pipeline
# =========================================================================

def test_write_model_is_atomic_and_manifested(tmp_path):
    net = _mlp()
    p = tmp_path / "ckpt.zip"
    ModelSerializer.write_model(net, p)
    ok, why = rck.verify_checkpoint(p)
    assert ok, why
    m = json.loads(rck.manifest_path(p).read_text())
    assert m["crc32"] == rck.file_crc32(p)
    assert m["size"] == p.stat().st_size
    assert m["format_version"] == rck.FORMAT_VERSION
    assert not list(tmp_path.glob(".*tmp*"))          # no droppings


def test_commit_fault_preserves_previous_checkpoint(tmp_path):
    """A crash after the tmp zip is written but before os.replace: the
    previous checkpoint survives untouched, no tmp file remains, and
    the restart loop restores the OLD state."""
    net = _mlp()
    p = tmp_path / "checkpoint_iter_1.zip"
    ModelSerializer.write_model(net, p)
    old_bytes = p.read_bytes()
    net.fit(_iter(_data(48)), epochs=1)
    with faults.active("ckpt_commit:error=OSError:nth=1"):
        with pytest.raises(OSError):
            ModelSerializer.write_model(net, p)
    assert p.read_bytes() == old_bytes
    assert not list(tmp_path.glob(".*tmp*"))
    assert newest_checkpoint(tmp_path) == p


def test_truncated_newest_falls_back_and_quarantines(tmp_path):
    """Satellite acceptance: truncate the newest checkpoint mid-byte →
    restore falls back to the previous valid one and the corrupt file
    is quarantined (counter incremented)."""
    net = _mlp()
    it = _iter(_data(48))
    a = tmp_path / "checkpoint_iter_2.zip"
    b = tmp_path / "checkpoint_iter_4.zip"
    net.fit(it, epochs=1)
    ModelSerializer.write_model(net, a)
    import jax
    good_params = jax.tree.map(np.asarray, net.params)  # donation-safe
    net.fit(it, epochs=1)
    ModelSerializer.write_model(net, b)
    os.utime(b, (time.time() + 5, time.time() + 5))   # decisively newest
    # truncate mid-byte (and refresh the manifest-free scenario: drop
    # the sidecar so the zip-level sweep has to catch it)
    data = b.read_bytes()
    b.write_bytes(data[:len(data) // 2])
    rck.manifest_path(b).unlink()
    q0 = _counter(metrics.CKPT_QUARANTINED)
    newest = newest_checkpoint(tmp_path)
    assert newest == a
    assert _counter(metrics.CKPT_QUARANTINED) == q0 + 1
    assert not b.exists()
    assert (tmp_path / "corrupt" / b.name).exists()
    back = resume_or_init(lambda: _mlp(), tmp_path)
    assert _params_equal(back.params, good_params)


def test_manifest_crc_mismatch_detected(tmp_path):
    """Bit-rot INSIDE a structurally-valid zip member is caught by the
    whole-file CRC in the manifest (testzip alone can miss flips in
    the compressed stream that still inflate)."""
    net = _mlp()
    p = tmp_path / "checkpoint_iter_1.zip"
    ModelSerializer.write_model(net, p)
    data = bytearray(p.read_bytes())
    data[len(data) // 2] ^= 0xFF                      # single-byte rot
    p.write_bytes(bytes(data))
    ok, why = rck.verify_checkpoint(p)
    assert not ok
    assert "crc" in why.lower() or "zip" in why.lower()


def test_corrupt_manifest_falls_back_to_zip_checks(tmp_path):
    net = _mlp()
    p = tmp_path / "checkpoint_iter_1.zip"
    ModelSerializer.write_model(net, p)
    rck.manifest_path(p).write_text("{torn json")
    ok, why = rck.verify_checkpoint(p)
    assert ok, why                                    # zip itself is fine


def test_sharded_restore_latest_valid_quarantines(tmp_path):
    """Orbax path: an unrestorable step dir is quarantined and restore
    falls back to the newest step that restores."""
    from deeplearning4j_tpu.serialization import ShardedCheckpointer
    net = _mlp()
    ck = ShardedCheckpointer(tmp_path, keep_last=3, async_save=False)
    ck.save(1, net, wait=True)
    p1 = np.asarray(next(iter(
        __import__("jax").tree.leaves(net.params))))
    net.fit(_iter(_data(48)), epochs=1)
    ck.save(2, net, wait=True)
    # corrupt step 2: truncate one tensorstore data file
    files = [f for f in (tmp_path / "2").rglob("*") if f.is_file()]
    for f in files:
        f.write_bytes(f.read_bytes()[:3])
    fresh = _mlp()
    q0 = _counter(metrics.CKPT_QUARANTINED)
    ck.restore_latest_valid(fresh)
    assert np.allclose(
        np.asarray(next(iter(__import__("jax").tree.leaves(
            fresh.params)))), p1)
    assert _counter(metrics.CKPT_QUARANTINED) == q0 + 1
    assert (tmp_path / "corrupt" / "2").exists()
    assert ck.all_steps() == [1]
    ck.close()


# =========================================================================
# retry / classification policy
# =========================================================================

def test_classify_table():
    assert classify(OSError("disk flake")) == "transient"
    assert classify(ConnectionError("chip dropped")) == "transient"
    assert classify(TimeoutError("collective stall")) == "transient"
    assert classify(RuntimeError("XLA runtime hiccup")) == "transient"
    assert classify(RuntimeError("dot_general shape mismatch")) \
        == "deterministic"
    assert classify(ValueError("incompatible dtype")) == "deterministic"
    assert classify(FloatingPointError("x")) == "deterministic"
    assert classify(RuntimeError("loss is NaN")) == "deterministic"
    assert classify(faults.InjectedFault("boom")) == "transient"


def test_retry_policy_backoff_shape():
    p = RetryPolicy(base_delay_s=0.1, max_delay_s=1.0, jitter=0.0)
    assert [p.delay(i) for i in (1, 2, 3, 4, 5, 6)] == \
        [0.1, 0.2, 0.4, 0.8, 1.0, 1.0]                # clamped
    j = RetryPolicy(base_delay_s=0.1, jitter=0.5, seed=4)
    assert j.delay(2) == j.delay(2)                   # seeded
    assert 0.1 <= j.delay(2) <= 0.3                   # within jitter band


def test_retry_policy_call_semantics():
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("flake")
        return "ok"

    p = RetryPolicy(max_retries=5, base_delay_s=0.01, jitter=0.0)
    assert p.call(flaky, sleep=slept.append) == "ok"
    assert calls["n"] == 3 and len(slept) == 2

    def det():
        raise ValueError("shape mismatch forever")

    calls["n"] = 0
    with pytest.raises(ValueError):
        p.call(det, sleep=slept.append)


# =========================================================================
# injected-fault matrix (acceptance): recovery + obs counters per site
# =========================================================================

@pytest.fixture(scope="module")
def uninterrupted_run():
    """One shared fault-free 4-epoch reference trajectory (params
    snapshot + loss) for every matrix entry."""
    import jax
    ds = _data()
    base = _mlp()
    base.fit(_iter(ds), epochs=4)
    return (jax.tree.map(np.asarray, base.params),
            float(base.score(ds)), ds)


@pytest.mark.parametrize("site,spec", [
    ("step", "step:error=ConnectionError:nth=6:max=1"),
    ("iterator", "iterator:error=OSError:nth=9:max=1"),
    ("ckpt_write", "ckpt_write:error=OSError:nth=3:max=1"),
])
def test_fault_matrix_training_recovers(site, spec, tmp_path,
                                        uninterrupted_run):
    """For each training-side fault site, a seeded plan produces
    recovery: the chaotic run reaches the uninterrupted run's loss
    (bit-equal params for clean restores) and the injection counter
    incremented."""
    base_params, base_loss, ds = uninterrupted_run
    it = _iter(ds)

    net = _mlp()
    trainer = FaultTolerantTrainer(net, tmp_path,
                                   save_every_n_iterations=2,
                                   max_restarts=6)
    f0 = _counter(metrics.FAULTS_INJECTED, site=site)
    r0 = _counter(metrics.RESILIENCE_RESTARTS)
    with faults.active(spec):
        trainer.fit(it, epochs=4)
        fired = sum(s["fires"] for s in faults.stats().values())
    assert fired == 1
    assert _counter(metrics.FAULTS_INJECTED, site=site) == f0 + 1
    assert _counter(metrics.RESILIENCE_RESTARTS) == r0 + trainer.restarts
    assert trainer.restarts >= 1
    assert net.epoch == 4
    loss = float(net.score(ds))
    assert np.isfinite(loss)
    assert abs(loss - base_loss) <= 0.05
    if site in ("step", "iterator"):
        # fault hit after checkpoints existed → exact-resume trajectory
        assert _params_equal(base_params, net.params, tol=1e-5)


def test_fault_matrix_serving_sheds_not_blocks():
    """Serving-side acceptance: under an injected worker fault the
    queue sheds/errors rather than blocking, the counter increments,
    and the SAME worker thread keeps serving afterwards."""
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    net = _mlp()
    pi = ParallelInference(net, batch_limit=4, queue_limit=8,
                           buckets=(1, 2, 4))
    x = np.random.RandomState(0).randn(8, 8).astype(np.float32)
    f0 = _counter(metrics.FAULTS_INJECTED, site="serving")
    with faults.active("serving:error=RuntimeError:nth=1:max=1"):
        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="injected fault"):
            pi.output(x[0], timeout=10.0)
        assert time.perf_counter() - t0 < 5.0         # fast error, no hang
    assert _counter(metrics.FAULTS_INJECTED, site="serving") == f0 + 1
    out = np.asarray(pi.output(x[1], timeout=10.0))   # worker survived
    assert out.shape[-1] == 3
    pi.shutdown()


def test_fault_matrix_worker_step_recovers(tmp_path):
    """ParallelWrapper fit loop site: FaultTolerantTrainer driving the
    wrapper (train_with=) restores and completes after an injected
    worker failure."""
    from deeplearning4j_tpu.parallel import ParallelWrapper
    ds = _data()
    it = _iter(ds)
    net = _mlp()
    pw = ParallelWrapper(net, mode=ParallelWrapper.SYNC,
                         prefetch_buffer=0)
    trainer = FaultTolerantTrainer(net, tmp_path,
                                   save_every_n_iterations=2,
                                   max_restarts=4, train_with=pw)
    f0 = _counter(metrics.FAULTS_INJECTED, site="worker_step")
    with faults.active("worker_step:error=ConnectionError:nth=6:max=1"):
        trainer.fit(it, epochs=3)
    assert _counter(metrics.FAULTS_INJECTED, site="worker_step") == f0 + 1
    assert trainer.restarts == 1
    assert net.epoch == 3
    assert np.isfinite(float(net.score(ds)))


def test_wrapper_crash_after_periodic_save_resumes_bit_equal(tmp_path):
    """The wrapper launches step n+1 before it calls step n's
    listeners; the periodic checkpoint and ``progress.json`` say that
    they read the net's state, so no step runs ahead of a saved one:
    a crash after a save restores a zip that holds exactly its
    iteration's weights and momentum, and the resumed run ends bit
    for bit where the uninterrupted one does."""
    import jax
    from deeplearning4j_tpu.parallel import ParallelWrapper
    ds = _data()

    def run(plan, where):
        net = _mlp()
        pw = ParallelWrapper(net, mode=ParallelWrapper.SYNC,
                             prefetch_buffer=0)
        trainer = FaultTolerantTrainer(net, where,
                                       save_every_n_iterations=2,
                                       max_restarts=4, train_with=pw)
        if plan is None:
            trainer.fit(_iter(ds), epochs=3)
        else:
            with faults.active(plan):
                trainer.fit(_iter(ds), epochs=3)
        return net, trainer

    base, _ = run(None, tmp_path / "base")
    # 4 batches an epoch: the 8th loop body is epoch 2's last, with
    # step 7 on the chips and the zip of iteration 6 on disk, written
    # by step 6's listeners in the body that launched step 7
    net, trainer = run("worker_step:error=ConnectionError:nth=8:max=1",
                       tmp_path / "crashed")
    assert trainer.restarts == 1
    assert net.iteration == base.iteration == 12 and net.epoch == 3
    # the tracker says so at the save's cadence
    reads = trainer._tracker.reads_state
    assert [reads(i) for i in (2, 3, 4)] == [True, False, True]
    for tree, want in ((net.params, base.params),
                       (net.opt_state, base.opt_state)):
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# =========================================================================
# serving load-shedding + deadlines + graceful drain
# =========================================================================

def _blocked_pi(net, queue_limit=4):
    """ParallelInference whose worker is parked on an event — queue
    fills deterministically."""
    import threading
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    pi = ParallelInference(net, batch_limit=4, queue_limit=queue_limit,
                           buckets=(1, 2, 4))
    release = threading.Event()
    real = pi._infer

    def gated(batch):
        release.wait(20.0)
        return real(batch)

    pi._infer = gated
    return pi, release


def test_queue_full_sheds_fast():
    from deeplearning4j_tpu.parallel.inference import QueueFullError
    net = _mlp()
    pi, release = _blocked_pi(net, queue_limit=4)
    x = np.zeros(8, np.float32)
    obs_ = []
    s0 = _counter(metrics.REQS_SHED, reason="queue_full")
    # park the worker on the first request...
    obs_.append(pi.output_async(x))
    for _ in range(200):
        if pi._q.qsize() == 0:
            break
        time.sleep(0.005)
    assert pi._q.qsize() == 0         # worker holds it, queue is empty
    # ...then fill the queue exactly to its bound
    for _ in range(4):
        obs_.append(pi.output_async(x))
    t0 = time.perf_counter()
    with pytest.raises(QueueFullError):
        pi.output_async(x)
    assert time.perf_counter() - t0 < 0.5             # shed, not blocked
    assert _counter(metrics.REQS_SHED, reason="queue_full") == s0 + 1
    release.set()
    for ob in obs_:
        assert np.asarray(ob.get(10.0)).shape[-1] == 3
    pi.shutdown()


def test_deadline_expired_requests_skipped_not_computed():
    from deeplearning4j_tpu.parallel.inference import DeadlineExpiredError
    net = _mlp()
    pi, release = _blocked_pi(net, queue_limit=8)
    x = np.zeros(8, np.float32)
    s0 = _counter(metrics.REQS_SHED, reason="deadline")
    blocker = pi.output_async(x)                      # parks the worker
    time.sleep(0.05)
    doomed = pi.output_async(x, deadline_s=0.01)      # expires in queue
    alive = pi.output_async(x, deadline_s=30.0)
    time.sleep(0.1)                                   # let deadline pass
    release.set()
    with pytest.raises(DeadlineExpiredError):
        doomed.get(10.0)
    assert np.asarray(alive.get(10.0)).shape[-1] == 3
    assert np.asarray(blocker.get(10.0)).shape[-1] == 3
    assert _counter(metrics.REQS_SHED, reason="deadline") == s0 + 1
    pi.shutdown()


def test_zero_deadline_means_expired_not_disabled():
    """Falsy-deadline regression (ISSUE 13 satellite): an EXPLICIT
    deadline of 0/0.0 means "already expired" — the worker must shed
    it, never compute it. The old ``if deadline_s`` truthiness test
    silently read 0 as "no deadline"."""
    from deeplearning4j_tpu.parallel.inference import (
        DeadlineExpiredError)
    net = _mlp()
    pi, release = _blocked_pi(net, queue_limit=8)
    x = np.zeros(8, np.float32)
    s0 = _counter(metrics.REQS_SHED, reason="deadline")
    blocker = pi.output_async(x)                      # parks the worker
    time.sleep(0.05)
    doomed = pi.output_async(x, deadline_s=0.0)       # already expired
    release.set()
    with pytest.raises(DeadlineExpiredError):
        doomed.get(10.0)
    assert np.asarray(blocker.get(10.0)).shape[-1] == 3
    assert _counter(metrics.REQS_SHED, reason="deadline") == s0 + 1
    # output()'s timeout doubles as the deadline: timeout=0 must also
    # mean expired (sheds in the worker; the caller's get times out)
    pi2, release2 = _blocked_pi(net, queue_limit=8)
    b2 = pi2.output_async(x)
    time.sleep(0.05)
    with pytest.raises(TimeoutError):
        pi2.output(x, timeout=0)
    release2.set()
    assert np.asarray(b2.get(10.0)).shape[-1] == 3
    for _ in range(400):    # worker sheds it on its NEXT loop pass
        if _counter(metrics.REQS_SHED, reason="deadline") == s0 + 2:
            break
        time.sleep(0.005)
    assert _counter(metrics.REQS_SHED, reason="deadline") == s0 + 2
    pi.shutdown()
    pi2.shutdown()


def test_shutdown_flushes_queue_immediately():
    """Satellite acceptance: queued observables must not wait out their
    full timeout — shutdown errors them out immediately."""
    from deeplearning4j_tpu.parallel.inference import ServingShutdownError
    net = _mlp()
    pi, release = _blocked_pi(net, queue_limit=8)
    x = np.zeros(8, np.float32)
    s0 = _counter(metrics.REQS_SHED, reason="shutdown")
    blocker = pi.output_async(x)
    time.sleep(0.05)
    queued = [pi.output_async(x) for _ in range(4)]
    release.set()                                     # let blocker finish
    t0 = time.perf_counter()
    drained = pi.shutdown(timeout=10.0)
    flush_errors = 0
    for ob in queued:
        try:
            ob.get(timeout=0.5)
        except ServingShutdownError:
            flush_errors += 1
    assert time.perf_counter() - t0 < 5.0             # no 30 s stall
    assert flush_errors == drained > 0
    assert _counter(metrics.REQS_SHED, reason="shutdown") >= s0 + drained
    # post-shutdown submissions refuse immediately
    with pytest.raises(ServingShutdownError):
        pi.output_async(x)


# =========================================================================
# preemption (SIGTERM): in-process + subprocess clean-exit fence
# =========================================================================

def test_preemption_checkpoints_and_stops_cleanly(tmp_path):
    """Self-delivered SIGTERM mid-fit (the `preempt` named plan): the
    trainer checkpoints at the iteration boundary and returns instead
    of dying; resume_or_init continues from the preemption point."""
    ds = _data()
    net = _mlp()
    trainer = FaultTolerantTrainer(net, tmp_path,
                                   save_every_n_iterations=2)
    p0 = _counter(metrics.PREEMPTIONS)
    with faults.active("step:error=sigterm:nth=5:max=1"):
        trainer.fit(_iter(ds), epochs=5)
    assert trainer.preempted
    assert _counter(metrics.PREEMPTIONS) == p0 + 1
    assert net.epoch < 5                              # stopped early...
    ck = newest_checkpoint(tmp_path)
    assert ck is not None
    ok, why = rck.verify_checkpoint(ck)
    assert ok, why
    prog = json.loads((tmp_path / "progress.json").read_text())
    assert prog["iteration"] == net.iteration
    back = resume_or_init(lambda: _mlp(), tmp_path)   # ...and resumes
    assert back.iteration == net.iteration
    t2 = FaultTolerantTrainer(back, tmp_path, save_every_n_iterations=2)
    t2.fit(_iter(ds), epochs=5 - back.epoch)
    assert back.epoch == 5


_SIGTERM_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.train.fault_tolerance import FaultTolerantTrainer

rng = np.random.RandomState(5)
x = rng.randn(96, 8).astype(np.float32)
y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 96)]
ds = DataSet(x, y)
it = ListDataSetIterator([b for b in ds.batch_by(24)], batch_size=24)
conf = (NeuralNetConfiguration.builder().seed(11)
        .updater(upd.Adam(learning_rate=5e-3)).list()
        .layer(DenseLayer(n_out=16, activation="tanh"))
        .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(8)).build())
net = MultiLayerNetwork(conf).init()


class Beacon:
    def iteration_done(self, net, iteration, epoch):
        print(f"ITER {iteration}", flush=True)
    def on_epoch_start(self, net):
        pass
    def on_epoch_end(self, net):
        pass


net.listeners.append(Beacon())
trainer = FaultTolerantTrainer(net, %(ckdir)r, save_every_n_iterations=2)
trainer.fit(it, epochs=500)                 # SIGTERM ends this early
print(json.dumps({"preempted": trainer.preempted,
                  "iteration": net.iteration}), flush=True)
"""


def test_sigterm_during_fit_exits_zero_with_valid_checkpoint(tmp_path):
    """Satellite acceptance: SIGTERM-during-fit subprocess test — a
    valid final checkpoint and exit code 0."""
    child = subprocess.Popen(
        [sys.executable, "-c",
         _SIGTERM_CHILD % {"repo": str(REPO), "ckdir": str(tmp_path)}],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    # wait until training demonstrably runs, then preempt
    saw_iters = 0
    for line in child.stdout:
        if line.startswith("ITER"):
            saw_iters += 1
            if saw_iters == 6:
                child.send_signal(signal.SIGTERM)
                break
    out, _ = child.communicate(timeout=120)
    assert child.returncode == 0, out
    tail = [l for l in out.splitlines() if l.startswith("{")]
    assert tail, out
    final = json.loads(tail[-1])
    assert final["preempted"] is True
    assert final["iteration"] >= 6
    ck = newest_checkpoint(tmp_path)
    assert ck is not None
    ok, why = rck.verify_checkpoint(ck)
    assert ok, why
    back = resume_or_init(lambda: _mlp(), tmp_path)
    assert back.iteration == final["iteration"]


# =========================================================================
# crash consistency: kill -9 at arbitrary points during save
# =========================================================================

_KILL9_CHILD = r"""
import sys
import numpy as np
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.nn import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.serialization import ModelSerializer

conf = (NeuralNetConfiguration.builder().seed(11)
        .updater(upd.Adam(learning_rate=5e-3)).list()
        .layer(DenseLayer(n_out=64, activation="tanh"))
        .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(8)).build())
net = MultiLayerNetwork(conf).init()
print("READY", flush=True)
i = 0
while True:                       # save continuously until killed
    i += 1
    net.iteration = i
    ModelSerializer.write_model(
        net, %(ckdir)r + f"/checkpoint_iter_{i %% 4}.zip")
    print(f"SAVED {i}", flush=True)
"""


def test_kill9_during_save_leaves_restorable_newest(tmp_path):
    """Acceptance: kill -9 at ANY point during save leaves either the
    old or the new checkpoint fully restorable — several kill times
    sampled across the save cycle, every survivor directory must hold
    a valid newest checkpoint."""
    for delay in (0.02, 0.075):
        d = tmp_path / f"run_{int(delay * 1000)}"
        d.mkdir()
        child = subprocess.Popen(
            [sys.executable, "-c",
             _KILL9_CHILD % {"repo": str(REPO), "ckdir": str(d)}],
            stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        saves = 0
        for line in child.stdout:
            if line.startswith("SAVED"):
                saves += 1
                if saves >= 2:
                    break
        time.sleep(delay)         # land the kill mid-save-cycle
        child.kill()              # SIGKILL: no cleanup code runs
        child.wait(timeout=60)
        child.stdout.close()
        ck = newest_checkpoint(d)
        assert ck is not None, f"no valid checkpoint after kill@{delay}"
        ok, why = rck.verify_checkpoint(ck)
        assert ok, f"kill@{delay}: {why}"
        back = ModelSerializer.restore_multi_layer_network(str(ck))
        assert back.iteration >= 1


# =========================================================================
# mid-epoch position + exact resume
# =========================================================================

def test_mid_epoch_restore_replays_exact_trajectory(tmp_path):
    """A fault mid-epoch-2 restores to the mid-epoch checkpoint, skips
    the already-trained batches (progress.json batch_in_epoch), and
    ends bit-identical to the uninterrupted run."""
    ds = _data()
    it = _iter(ds)                                    # 4 batches/epoch
    base = _mlp()
    base.fit(it, epochs=3)

    net = _mlp()
    trainer = FaultTolerantTrainer(net, tmp_path,
                                   save_every_n_iterations=2,
                                   max_restarts=3)
    # 7th step = batch 3 of epoch 2; newest ckpt iter 6 (batch 2),
    # restore must skip exactly 2 batches
    with faults.active("step:error=ConnectionError:nth=7:max=1"):
        trainer.fit(it, epochs=3)
    assert trainer.restarts == 1
    assert net.iteration == base.iteration == 12
    assert _params_equal(base.params, net.params, tol=1e-5)

"""Elastic multi-host training (ARCHITECTURE.md §13,
resilience/elastic.py): membership coordinator with generation-
numbered mesh epochs, bounded-timeout collectives, exec-based mesh
re-formation, and resharded restore — plus the PR 5 × PR 3 interplay
(SIGTERM under a ZeRO sharded wrapper publishes a SHARDED checkpoint)
and the multi-host chaos drill on tests/mp_harness.py.
"""
import os
import sys
import time
from pathlib import Path

import jax
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
from deeplearning4j_tpu.resilience import elastic, faults

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.reset()
    yield
    faults.reset()


def _mlp(seed=11, n_in=8, n_out=3, hidden=16):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Adam(learning_rate=5e-3)).list()
            .layer(DenseLayer(n_out=hidden, activation="tanh"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _iter(n=48, batch=8, seed=5, n_in=8, n_out=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.randint(0, n_out, n)]
    return ListDataSetIterator(DataSet(x, y), batch_size=batch)


# =========================================================================
# bounded-timeout collectives
# =========================================================================

def test_bounded_sync_value_error_and_timeout():
    assert elastic.bounded_sync(lambda: 41 + 1, 5.0) == 42
    with pytest.raises(ValueError, match="boom"):
        elastic.bounded_sync(
            lambda: (_ for _ in ()).throw(ValueError("boom")), 5.0)
    t0 = time.perf_counter()
    with pytest.raises(elastic.CollectiveTimeoutError,
                       match="re-form"):
        elastic.bounded_sync(lambda: time.sleep(30), 0.2,
                             what="unit probe")
    assert time.perf_counter() - t0 < 5.0   # raised, did not wait out
    # timeout 0/None = straight call (no watchdog thread)
    assert elastic.bounded_sync(lambda: "x", 0) == "x"


# =========================================================================
# membership coordinator: leases, eviction, agreement, epochs
# =========================================================================

def _clockpair(start=1000.0):
    t = [start]
    return t, (lambda: t[0])


def test_two_hosts_agree_then_evict_missed_lease(tmp_path):
    """Formation at epoch 1, then host b misses its lease: a alone
    commits epoch 2 without b, the eviction is counted, and b's stale
    context is rejected by the epoch stamp."""
    import threading
    t, clock = _clockpair()
    a = elastic.MembershipCoordinator(tmp_path, "a", lease_secs=5.0,
                                      clock=clock, port_base=31000)
    b = elastic.MembershipCoordinator(tmp_path, "b", lease_secs=5.0,
                                      clock=clock, port_base=31000)
    a.renew()
    b.renew()
    assert a.live_members() == ["a", "b"]
    recs = {}
    th = threading.Thread(
        target=lambda: recs.__setitem__("a", a.agree_membership(10.0)))
    th.start()
    recs["b"] = b.agree_membership(10.0)
    th.join(timeout=30)
    assert recs["a"]["epoch"] == recs["b"]["epoch"] == 1
    assert sorted(recs["a"]["members"]) == ["a", "b"]
    assert recs["a"]["coordinator"] == "a"      # deterministic leader
    assert a.rank_of(recs["a"]) == 0 and b.rank_of(recs["b"]) == 1
    ctx_b = elastic.ElasticContext(b, recs["b"])

    # b goes silent; its lease expires after the window
    e0 = metrics.HOSTS_EVICTED._children[()].get()
    t[0] += 6.0
    a.renew()
    rec2 = a.agree_membership(10.0)
    assert rec2["epoch"] == 2 and rec2["members"] == ["a"]
    assert metrics.HOSTS_EVICTED._children[()].get() == e0 + 1
    assert (tmp_path / "members" / "evicted").is_dir()
    # epoch-salted port moved with the generation
    assert rec2["port"] != recs["a"]["port"]

    # the straggler's next step is rejected, not silently absorbed
    with pytest.raises(elastic.StaleMeshEpoch, match="epoch 2"):
        ctx_b.pre_step(0)


def test_agreement_with_dotted_host_ids(tmp_path):
    """Host ids are arbitrary strings — hostnames with dots must ack
    cleanly (the ack files are parsed by prefix, not Path.suffix)."""
    import threading
    t, clock = _clockpair()
    a = elastic.MembershipCoordinator(tmp_path, "node.a.example",
                                      lease_secs=5.0, clock=clock)
    b = elastic.MembershipCoordinator(tmp_path, "node.b.example",
                                      lease_secs=5.0, clock=clock)
    a.renew()
    b.renew()
    recs = {}
    th = threading.Thread(
        target=lambda: recs.__setitem__("a", a.agree_membership(10.0)))
    th.start()
    recs["b"] = b.agree_membership(10.0)
    th.join(timeout=30)
    assert sorted(recs["a"]["members"]) == ["node.a.example",
                                           "node.b.example"]
    assert recs["a"]["epoch"] == 1


def test_agreement_supersedes_proposal_naming_dead_member(tmp_path):
    """A proposal whose member died before acking must be SUPERSEDED,
    not waited on forever: the leader re-proposes the current live
    set at the same generation and stale-set acks don't count."""
    import threading
    t, clock = _clockpair()
    mk = lambda h: elastic.MembershipCoordinator(
        tmp_path, h, lease_secs=5.0, clock=clock, port_base=31000)
    a, b, c = mk("a"), mk("b"), mk("c")
    for co in (a, b, c):
        co.renew()
    # a stale pre-crash proposal names all three; c dies before acking
    elastic._write_json(tmp_path / "proposals" / "1.json",
                        {"epoch": 1, "members": ["a", "b", "c"],
                         "coordinator": "a", "addr": "127.0.0.1",
                         "port": 31001})
    t[0] += 6.0                     # c's lease expires
    # (so have a's and b's, on this clock: their heartbeats renew them.
    # Left expired, whichever of the two agrees first evicts the other
    # and commits alone, and the other then waits for its ack on a
    # clock that stands still: a wrong epoch, or a run cut at its time
    # limit, on a loaded machine)
    a.renew()
    b.renew()
    recs = {}
    th = threading.Thread(
        target=lambda: recs.__setitem__("a", a.agree_membership(15.0)))
    th.start()
    recs["b"] = b.agree_membership(15.0)
    th.join(timeout=40)
    assert recs["a"]["epoch"] == 1
    assert sorted(recs["a"]["members"]) == ["a", "b"]
    assert recs["a"] == recs["b"]


def test_graceful_leave_evicts_without_lease_wait(tmp_path):
    t, clock = _clockpair()
    a = elastic.MembershipCoordinator(tmp_path, "a", lease_secs=50.0,
                                      clock=clock)
    b = elastic.MembershipCoordinator(tmp_path, "b", lease_secs=50.0,
                                      clock=clock)
    a.renew()
    b.renew()
    b.leave()                       # SIGTERM path: no lease to wait out
    assert a.live_members() == ["a"]
    rec = a.agree_membership(10.0)
    assert rec["members"] == ["a"] and rec["epoch"] == 1


def test_join_settles_and_commits(tmp_path):
    """join(expected=N) forms as soon as all leases exist; the epoch
    gauge reflects the committed generation."""
    import threading
    t, clock = _clockpair()
    a = elastic.MembershipCoordinator(tmp_path, "a", lease_secs=5.0,
                                      clock=clock)
    b = elastic.MembershipCoordinator(tmp_path, "b", lease_secs=5.0,
                                      clock=clock)
    out = {}
    th = threading.Thread(
        target=lambda: out.__setitem__("a", a.join(expected=2,
                                                   timeout_s=20)))
    th.start()
    out["b"] = b.join(expected=2, timeout_s=20)
    th.join(timeout=30)
    assert out["a"]["epoch"] == out["b"]["epoch"] == 1
    assert metrics.MESH_EPOCH._children[()].get() == 1.0


def test_lease_ages_surface_on_healthz(tmp_path):
    """The coordinator mirrors peer lease ages into obs/health.py —
    a dead peer is named by the PR 2 scrape surface."""
    from deeplearning4j_tpu.obs import health
    health.reset()
    t, clock = _clockpair()
    a = elastic.MembershipCoordinator(tmp_path, "a", lease_secs=5.0,
                                      clock=clock)
    b = elastic.MembershipCoordinator(tmp_path, "b", lease_secs=5.0,
                                      clock=clock)
    b.renew()
    t[0] += 40.0                    # b silent for 40s
    a.renew()
    chk = health.check(stale_after=30.0)
    assert not chk["host:a"]["stale"]
    assert chk["host:b"]["stale"]
    assert chk["host:b"]["age_s"] >= 39.0
    health.reset()


def test_fault_sites_host_death_and_coordinator(tmp_path):
    """The elastic layer's injection sites fire like every other
    failure mode, and the named host-preempt plan parses."""
    assert faults.FaultPlan.parse("host-preempt")
    t, clock = _clockpair()
    co = elastic.MembershipCoordinator(tmp_path, "a", lease_secs=5.0,
                                       clock=clock)
    co.renew()
    rec_stub = {"epoch": 0, "members": ["a"], "port": 1}
    # commit epoch 0 == coordinator's view (no epoch.json -> 0)
    ctx = elastic.ElasticContext(co, rec_stub)
    with faults.active("host_death:error=InjectedFault:nth=1"):
        with pytest.raises(faults.InjectedFault):
            ctx.pre_step(0)
    with faults.active("coordinator:error=OSError:nth=1"):
        with pytest.raises(OSError):
            co.renew()


def test_elastic_env_is_epoch_salted():
    rec = {"epoch": 3, "members": ["h0", "h1"], "addr": "127.0.0.1",
           "port": 31303}
    env = elastic.elastic_env(rec)
    assert env["DL4J_TPU_COORD"] == "127.0.0.1:31303"
    assert env["DL4J_TPU_NPROC"] == "2"


def test_coordinator_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_ELASTIC_DIR", str(tmp_path / "el"))
    monkeypatch.setenv("DL4J_TPU_HOST_ID", "envhost")
    monkeypatch.setenv("DL4J_TPU_HOST_LEASE_SECS", "7.5")
    co = elastic.MembershipCoordinator.from_env()
    assert co.host == "envhost" and co.lease_secs == 7.5
    co.renew()
    assert co.live_members() == ["envhost"]
    monkeypatch.delenv("DL4J_TPU_ELASTIC_DIR")
    with pytest.raises(ValueError, match="DL4J_TPU_ELASTIC_DIR"):
        elastic.MembershipCoordinator.from_env()


# =========================================================================
# reshard repad: bit-identity both directions
# =========================================================================

def test_repad_flat_leaves_bit_identity_8_to_4_to_8():
    from deeplearning4j_tpu.parallel.zero import repad_flat_leaves
    rng = np.random.RandomState(0)
    sizes = [10, 64, 7, 1]
    pad = lambda s, n: ((s + n - 1) // n) * n
    src8 = []
    for s in sizes:
        v = np.zeros(pad(s, 8), np.float32)
        v[:s] = rng.randn(s)
        src8.append(v)
    ref4 = [np.zeros(pad(s, 4), np.float32) for s in sizes]
    ref8 = [np.zeros(pad(s, 8), np.float32) for s in sizes]
    at4 = repad_flat_leaves(src8, ref4)
    back8 = repad_flat_leaves(at4, ref8)
    for a, b in zip(src8, back8):
        assert a.shape == b.shape
        assert np.array_equal(a, b)          # bit-identical round trip
    # scalars pass through untouched
    assert repad_flat_leaves([np.float32(3.0)],
                             [np.zeros((), np.float32)])[0] == 3.0
    # a non-zero tail is a layout mismatch, not data to drop silently
    bad = np.ones(16, np.float32)
    with pytest.raises(ValueError, match="non-zero"):
        repad_flat_leaves([bad], [np.zeros(12, np.float32)])


# =========================================================================
# harness: N workers + deterministic kill_after
# =========================================================================

def test_mp_harness_kill_after(tmp_path):
    """The generalized harness SIGKILLs the requested worker on
    schedule and still reaps everyone (no jax involved — this is the
    scaffolding other drills stand on)."""
    from mp_harness import run_workers
    script = tmp_path / "w.py"
    script.write_text(
        "import os, time\n"
        "if os.environ['PROC_ID'] == '2':\n"
        "    time.sleep(60)\n"
        "print('proc %s DONE' % os.environ['PROC_ID'], flush=True)\n")
    t0 = time.perf_counter()
    procs, outs = run_workers(script, port=29999, n=3,
                              kill_after={2: 1.0}, timeout=30)
    assert time.perf_counter() - t0 < 30
    assert procs[0].returncode == 0 and "proc 0 DONE" in outs[0]
    assert procs[1].returncode == 0 and "proc 1 DONE" in outs[1]
    assert procs[2].returncode == -9


# =========================================================================
# PR 5 x PR 3 interplay: SIGTERM under a ZeRO wrapper -> SHARDED publish
# =========================================================================

def test_preempt_sharded_wrapper_publishes_sharded_and_resumes_bitexact(
        tmp_path):
    """SIGTERM mid-fit with sharded_update=True publishes through
    ShardedCheckpointer.save_wrapper (1/N shards, world manifest) —
    NOT the replicated zip path — and a fresh process resuming from it
    replays the uninterrupted trajectory bit-exactly."""
    from deeplearning4j_tpu.serialization import ShardedCheckpointer
    from deeplearning4j_tpu.train.fault_tolerance import (
        FaultTolerantTrainer)

    def drive(trainer_dir, plan, epochs, net):
        from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
        w = ParallelWrapper(net, workers=2, sharded_update=True,
                            prefetch_buffer=0)
        tr = FaultTolerantTrainer(net, trainer_dir,
                                  save_every_n_iterations=3,
                                  train_with=w)
        if plan:
            with faults.active(plan):
                tr.fit(_iter(), epochs=epochs)
        else:
            tr.fit(_iter(), epochs=epochs)
        return tr, w

    d = tmp_path / "ck"
    net = _mlp()
    # 6 batches/epoch; SIGTERM at the 5th worker step -> mid-epoch 0
    tr, w = drive(d, "worker_step:error=sigterm:nth=5:max=1", 3, net)
    assert tr.preempted
    stop_iter = net.iteration
    assert stop_iter == 5
    sh = ShardedCheckpointer(d / "sharded", async_save=False)
    assert sh.all_steps() and max(sh.all_steps()) == stop_iter
    wm = sh.world_manifest(stop_iter)
    assert wm["n_shards"] == 2 and wm["layout"] == "zero-flat"
    # the preemption did NOT go through the replicated zip path: the
    # newest zip is an older periodic save from the listener
    from deeplearning4j_tpu.train.fault_tolerance import (
        newest_checkpoint)
    zips = newest_checkpoint(d)
    assert zips is None or \
        FaultTolerantTrainer._zip_iteration(zips) < stop_iter
    sh.close()

    # fresh process image: new net + wrapper + trainer resume from the
    # SHARDED chain (it is newer than any zip) and finish the budget
    net2 = _mlp()
    tr2, w2 = drive(d, None, 3, net2)   # target = restored epoch + 3
    # wait: restored epoch is 0 (preempt mid-epoch 0) -> 3 epochs total
    assert net2.epoch == 3

    # uninterrupted baseline: same seed, same wrapper shape, no faults
    net3 = _mlp()
    _, w3 = drive(tmp_path / "base", None, 3, net3)
    assert net3.epoch == 3 and net3.iteration == net2.iteration
    for a, b in zip(jax.tree_util.tree_leaves(net2.params),
                    jax.tree_util.tree_leaves(net3.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# =========================================================================
# the multi-host chaos drill (mp_harness; slow — the acceptance fence)
# =========================================================================

@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("DL4J_TPU_SKIP_MP") == "1",
                    reason="multi-process test disabled")
def test_elastic_drill_sigkill_reform_reshard_baseline():
    """ISSUE 7 acceptance: SIGKILL one of three hosts mid-epoch →
    survivors raise out of the dead collective within the lease
    window, re-form the mesh at world size 2 (mesh epoch 2),
    reshard-restore the newest valid checkpoint (6 shards → 4), and
    the post-recovery trajectory is bit-identical to the same-scale
    uninterrupted baseline; mesh-epoch/eviction/restart metrics are
    exported. ISSUE 12 rides the same drill: a flight-recorder bundle
    must exist whose skew series names the killed host as the
    final-step straggler, the leader's eviction bundle must carry the
    corpse's final telemetry, and the surviving epoch's fleet
    exposition must carry mesh_epoch=2 labels."""
    sys.path.insert(0, str(REPO / "tools"))
    import chaos
    res = chaos._elastic_scenario(hosts=3, kill_host=2,
                                  port=29300 + (os.getpid() % 300))
    assert res["ok"], res
    assert res["victim_rc"] == -9
    assert res["survivor_world"] == 2 and res["mesh_epoch"] == 2
    assert res["resumed_step"] and res["resumed_step"] > 0
    assert res["detect_s"] <= 4 * res["lease_s"]
    assert res["trajectory_match"] is True
    assert res["hosts_evicted"] >= 1 and res["restarts"] >= 1
    # fleet observability plane (obs/fleet.py, ISSUE 12)
    assert res["flight_bundles"] >= 2          # survivor dump + evict
    assert res["straggler_final"] == "h2"      # the corpse, named
    assert res["evict_bundle_named_dead"] is True
    assert res["dead_last_step"] and res["dead_last_step"] > 0
    assert res["fleet_epoch2"] is True


@pytest.mark.slow
@pytest.mark.skipif(os.environ.get("DL4J_TPU_SKIP_MP") == "1",
                    reason="multi-process test disabled")
def test_elastic_host_preempt_named_plan_drill():
    """DL4J_TPU_FAULT_PLAN=host-preempt on one host of a live fleet:
    the victim gets SIGTERM at its nth elastic step, leaves
    gracefully (lease dropped), and the survivors re-form and finish."""
    sys.path.insert(0, str(REPO / "tools"))
    import chaos
    res = chaos._elastic_preempt_scenario(
        hosts=2, port=29650 + (os.getpid() % 200))
    assert res["ok"], res
    assert res["victim_preempted"] is True
    assert res["survivors_done"] == 1

"""Fleet observability plane (ARCHITECTURE.md §14, obs/fleet.py):
per-host telemetry snapshots on the elastic file plane, fleet-level
exposition aggregation with host=/mesh_epoch= labels, collective-skew
straggler attribution, and the crash flight recorder — plus the
heartbeat-plane unification (lease ages and worker beats share ONE
staleness table on /healthz) and the off-path zero-publish fence.
"""
import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.obs import fleet, health, metrics
from deeplearning4j_tpu.resilience import elastic, faults

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_planes():
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


def _iter(n=32, batch=8, seed=5, n_in=8, n_out=3):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rng.randint(0, n_out, n)]
    return ListDataSetIterator(DataSet(x, y), batch_size=batch)


def _clockpair(start=1000.0):
    t = [start]
    return t, (lambda: t[0])


# =========================================================================
# telemetry publishing: atomic, versioned, cadence-gated
# =========================================================================

def test_snapshot_publish_versioned_and_parseable(tmp_path):
    ft = fleet.FleetTelemetry(tmp_path, "h0", every_s=0.0)
    base = time.time()
    ft.note_enter(3, t=base)
    ft.record_step(3, mesh_epoch=2, t_exit=base + 0.01, loss=0.75)
    snap = json.loads((tmp_path / "telemetry" / "h0.json").read_text())
    assert snap["version"] == fleet.SNAPSHOT_VERSION
    assert snap["host"] == "h0" and snap["pid"] == os.getpid()
    assert snap["step"] == 3 and snap["mesh_epoch"] == 2
    (b,) = snap["barriers"]
    assert b[0] == 3 and b[2] - b[1] == pytest.approx(0.01, abs=1e-6)
    # the embedded exposition is valid Prometheus text
    fams = metrics.parse_exposition(snap["exposition"])
    assert any(k[0].startswith("dl4j_tpu_") for k in fams)
    # round trip through the reader (version-compatible)
    assert "h0" in fleet.read_snapshots(tmp_path)


def test_publish_cadence_is_gated_by_clock(tmp_path):
    t, clock = _clockpair()
    ft = fleet.FleetTelemetry(tmp_path, "h0", every_s=10.0,
                              clock=clock)
    p0 = fleet.publishes()
    ft.record_step(0)               # first record always publishes
    for i in range(1, 6):
        t[0] += 1.0
        ft.record_step(i)           # inside the cadence window
    assert fleet.publishes() == p0 + 1
    t[0] += 10.0
    ft.record_step(6)               # window elapsed
    assert fleet.publishes() == p0 + 2
    # step/barriers in the published file reflect the LAST publish
    snap = json.loads((tmp_path / "telemetry" / "h0.json").read_text())
    assert snap["step"] == 6


def test_incompatible_snapshot_version_skipped(tmp_path):
    ft = fleet.FleetTelemetry(tmp_path, "ok", every_s=0.0)
    ft.record_step(1)
    bad = tmp_path / "telemetry" / "zombie.json"
    bad.write_text(json.dumps({"version": 999, "host": "zombie",
                               "step": 9}))
    (tmp_path / "telemetry" / "torn.json").write_text("{not json")
    snaps = fleet.read_snapshots(tmp_path)
    assert set(snaps) == {"ok"}     # incompatible + torn both skipped


# =========================================================================
# aggregation: fleet exposition with host=/mesh_epoch= labels
# =========================================================================

def test_aggregate_exposition_carries_host_and_epoch_labels(tmp_path):
    base = time.time()
    for i, host in enumerate(("h0", "h1")):
        ft = fleet.FleetTelemetry(tmp_path, host, every_s=0.0)
        ft.record_step(5, mesh_epoch=3, t_enter=base + 0.01 * i,
                       t_exit=base + 0.02, loss=0.5)
    view = fleet.aggregate(tmp_path)
    assert set(view.table()) == {"h0", "h1"}
    text = view.exposition()
    fams = metrics.parse_exposition(text)      # raises on malformed
    hosts = {dict(labels).get("host") for (_n, labels) in fams}
    assert {"h0", "h1"} <= hosts
    # every MERGED per-host sample carries the mesh_epoch label (the
    # aggregator's own families — skew, ages — are per-host only)
    assert all(dict(labels).get("mesh_epoch") == "3"
               for (name, labels) in fams
               if dict(labels).get("host") in ("h0", "h1")
               and name not in fleet.AGGREGATE_FAMILIES)
    assert any(name == "dl4j_tpu_fleet_snapshots_published_total"
               and dict(labels).get("mesh_epoch") == "3"
               for (name, labels) in fams)
    assert fams[("dl4j_tpu_fleet_hosts", ())] == 2.0
    # TYPE lines come from the FAMILIES registry, including the
    # aggregator-computed families
    assert "# TYPE dl4j_tpu_collective_skew_seconds gauge" in text
    assert "# TYPE dl4j_tpu_fleet_snapshots_published_total counter" \
        in text


def test_skew_report_names_last_in_host(tmp_path):
    base = time.time()
    for host, late in (("h0", 0.0), ("h1", 0.04), ("h2", 0.002)):
        ft = fleet.FleetTelemetry(tmp_path, host, every_s=0.0)
        for step in (4, 5):
            ft.record_step(step, t_enter=base + step + late,
                           t_exit=base + step + late + 0.01)
    rep = fleet.aggregate(tmp_path).skew_report()
    assert rep["step"] == 5 and rep["missing"] == []
    assert rep["straggler"] == "h1"
    assert rep["skew_s"]["h1"] == pytest.approx(0.04, abs=1e-5)
    assert rep["skew_s"]["h0"] == 0.0
    # the per-step series names the last-in host step by step
    assert [s[0] for s in rep["series"]] == [4, 5]
    assert all(s[2] == "h1" for s in rep["series"])


def test_skew_names_lease_dead_host_as_final_step_straggler(tmp_path):
    """A host whose LEASE evidence says it is gone (lease older than
    its own window) is the straggler — entry times alone cannot tell
    the corpse from peers wedged waiting on it."""
    t, clock = _clockpair()
    co = {h: elastic.MembershipCoordinator(tmp_path, h, lease_secs=5.0,
                                           clock=clock)
          for h in ("h0", "h1", "h2")}
    fts = {h: fleet.FleetTelemetry(tmp_path, h, every_s=0.0,
                                   clock=clock)
           for h in ("h0", "h1", "h2")}
    for h in fts:
        co[h].renew()
        fts[h].record_step(7, t_enter=t[0], t_exit=t[0])
    # h2 dies; its lease outlives its window while the survivors
    # renew and enter step 8
    t[0] += 6.0
    for h in ("h0", "h1"):
        co[h].renew()
        fts[h].record_step(8, t_enter=t[0], t_exit=t[0])
    rep = fleet.aggregate(tmp_path, now=t[0]).skew_report()
    assert rep["step"] == 8
    assert rep["dead"] == ["h2"]
    assert rep["missing"] == ["h2"]
    assert rep["straggler"] == "h2"
    assert rep["skew_s"]["h2"] >= 0.0


def test_skew_no_phantom_straggler_on_staggered_cadence(tmp_path):
    """The healthy-fleet case: every lease live but snapshots lag one
    another by up to the publish cadence (step time ≪ cadence). The
    host with the staler snapshot must NOT be called missing or
    straggler — attribution anchors on the newest COMMON step."""
    t, clock = _clockpair()
    for h in ("h0", "h1"):
        co = elastic.MembershipCoordinator(tmp_path, h, lease_secs=30.0,
                                           clock=clock)
        co.renew()
    ft0 = fleet.FleetTelemetry(tmp_path, "h0", every_s=0.0, clock=clock)
    ft1 = fleet.FleetTelemetry(tmp_path, "h1", every_s=0.0, clock=clock)
    # h1's snapshot stops at step 10; h0's is ~1s fresher (step 13),
    # entering each step 1ms after h1 — the real skew is 1ms
    for s in range(8, 11):
        ft1.record_step(s, t_enter=1000.0 + s * 0.05,
                        t_exit=1000.0 + s * 0.05 + 0.01)
    for s in range(8, 14):
        ft0.record_step(s, t_enter=1000.0 + s * 0.05 + 0.001,
                        t_exit=1000.0 + s * 0.05 + 0.011)
    t[0] += 1.0
    rep = fleet.aggregate(tmp_path, now=t[0]).skew_report()
    assert rep["dead"] == [] and rep["missing"] == []
    assert rep["step"] == 10            # newest step BOTH published
    assert rep["straggler"] == "h0"     # the genuine 1ms last-in
    assert rep["max_skew_s"] == pytest.approx(0.001, abs=1e-5)


# =========================================================================
# crash flight recorder
# =========================================================================

def test_flight_recorder_ring_bounded_and_bundle_versioned(tmp_path):
    from deeplearning4j_tpu.obs.numerics import NonFiniteError
    ft = fleet.FleetTelemetry(tmp_path, "h0", every_s=1e9, ring=8)
    for i in range(50):
        ft.record_step(i, mesh_epoch=1, loss=1.0 / (i + 1))
    ft.event("mesh_epoch_commit", epoch=2)
    d0 = fleet.dumps()
    path = ft.dump(NonFiniteError(layer="dense_1", kind="gradients",
                                  iteration=49))
    assert fleet.dumps() == d0 + 1
    bundle = json.loads(Path(path).read_text())
    assert bundle["version"] == fleet.BUNDLE_VERSION
    assert bundle["host"] == "h0" and bundle["step"] == 49
    assert bundle["cause"] == "NonFiniteError"
    assert bundle["origin"] == {"layer": "dense_1",
                                "kind": "gradients", "iteration": 49}
    # bounded black box: ring + the epoch event, last-N only
    assert len(bundle["ring"]) == 8
    assert bundle["ring"][-1]["event"] == "mesh_epoch_commit"
    assert bundle["ring"][-2]["step"] == 49
    # the bundle carries the obs report tail and the fleet skew view
    assert "metrics" in bundle["report"]
    assert bundle["fleet"]["skew"]["step"] == 49


def test_leader_eviction_bundle_snapshots_dead_host(tmp_path):
    t, clock = _clockpair()
    dead = fleet.FleetTelemetry(tmp_path, "h9", every_s=0.0,
                                clock=clock)
    dead.record_step(12, mesh_epoch=1, loss=0.3)
    path = fleet.record_eviction(tmp_path, "h9", by="h0", now=t[0] + 6)
    bundle = json.loads(Path(path).read_text())
    assert bundle["cause"] == "Evicted" and bundle["host"] == "h9"
    assert bundle["recorded_by"] == "h0"
    assert bundle["final_telemetry"]["step"] == 12
    # the adjudicated skew view rides the eviction bundle
    assert bundle["fleet"]["skew"]["step"] == 12
    # the corpse's live snapshot retired from the fleet view, its
    # eviction visible to the watcher
    assert "h9" not in fleet.read_snapshots(tmp_path)
    view = fleet.aggregate(tmp_path)
    assert view.evicted() == ["h9"]
    # a host that never published: no-op, no bundle
    assert fleet.record_eviction(tmp_path, "ghost", by="h0") is None


def test_graceful_departure_retires_snapshot_not_straggler(tmp_path):
    """A host that LEAVES cleanly (SIGTERM path) retires its own
    snapshot into a departed bundle — without this, its lease-less
    stale snapshot would read as a corpse and be named straggler
    forever, masking any real one."""
    t, clock = _clockpair()
    co = {h: elastic.MembershipCoordinator(tmp_path, h, lease_secs=5.0,
                                           clock=clock)
          for h in ("h0", "h1", "h2")}
    for h, late in (("h0", 0.0), ("h1", 0.01), ("h2", 0.0)):
        co[h].renew()
        ft = fleet.FleetTelemetry(tmp_path, h, every_s=0.0,
                                  clock=clock)
        ft.record_step(3, t_enter=t[0] + late, t_exit=t[0] + late)
    co["h2"].leave()
    assert "h2" not in fleet.read_snapshots(tmp_path)
    bundles = list((tmp_path / "postmortem").glob("h2.departed.*.json"))
    assert len(bundles) == 1
    bundle = json.loads(bundles[0].read_text())
    assert bundle["cause"] == "Departed" and bundle["host"] == "h2"
    assert bundle["final_telemetry"]["step"] == 3
    # an hour later the fleet view names the REAL straggler, not the
    # long-departed host
    t[0] += 3600.0
    for h in ("h0", "h1"):
        co[h].renew()
    rep = fleet.aggregate(tmp_path, now=t[0]).skew_report()
    assert rep["dead"] == []
    assert rep["straggler"] == "h1"


def test_evicted_dump_does_not_resurrect_retired_snapshot(tmp_path):
    """An evicted host's own dump (republish=False) must not rewrite
    the telemetry file the leader's eviction bundle just retired —
    that lease-less snapshot would read as a corpse forever."""
    ft = fleet.FleetTelemetry(tmp_path, "hX", every_s=0.0)
    ft.record_step(5, mesh_epoch=1)
    fleet.record_eviction(tmp_path, "hX", by="h0")
    assert "hX" not in fleet.read_snapshots(tmp_path)
    path = ft.dump(RuntimeError("evicted straggler"), republish=False)
    assert path and Path(path).is_file()          # the bundle exists
    assert "hX" not in fleet.read_snapshots(tmp_path)   # still gone


def test_skew_disjoint_windows_name_no_straggler(tmp_path):
    """Steps much faster than the cadence: the hosts' barrier windows
    don't overlap, nobody is dead — a lone entrant at the newest step
    must NOT be named straggler (that would flag the FASTEST host)."""
    ft0 = fleet.FleetTelemetry(tmp_path, "h0", every_s=0.0)
    ft1 = fleet.FleetTelemetry(tmp_path, "h1", every_s=0.0)
    for s in range(100, 116):
        ft0.record_step(s, t_enter=1000.0 + s, t_exit=1000.0 + s)
    for s in range(40, 56):
        ft1.record_step(s, t_enter=1000.0 + s, t_exit=1000.0 + s)
    rep = fleet.aggregate(tmp_path, now=1200.0).skew_report()
    assert rep["dead"] == [] and rep["missing"] == []
    assert rep["straggler"] is None
    # and the exposition still parses with no straggler flagged
    text = fleet.aggregate(tmp_path, now=1200.0).exposition()
    fams = metrics.parse_exposition(text)
    flagged = [k for k, v in fams.items()
               if k[0] == "dl4j_tpu_collective_straggler" and v == 1.0]
    assert flagged == []


def test_dump_fleet_view_stays_in_injected_clock_domain(tmp_path):
    """dump() aggregates with the publisher's own clock — mixing a
    fake clock's stamps with wall time would make every age
    astronomically stale and every host read dead."""
    t, clock = _clockpair()
    co = elastic.MembershipCoordinator(tmp_path, "h0", lease_secs=5.0,
                                       clock=clock)
    co.renew()
    ft = fleet.FleetTelemetry(tmp_path, "h0", every_s=0.0, clock=clock)
    ft.record_step(2, t_enter=t[0], t_exit=t[0])
    bundle = json.loads(Path(ft.dump("probe")).read_text())
    assert bundle["fleet"]["skew"]["dead"] == []
    assert bundle["fleet"]["hosts"]["h0"]["age_s"] < 10.0


def test_coordinator_eviction_writes_leader_bundle(tmp_path):
    """The wired path: MembershipCoordinator.evict_expired — the
    winner of the lease race snapshots the dead host's telemetry."""
    t, clock = _clockpair()
    a = elastic.MembershipCoordinator(tmp_path, "a", lease_secs=5.0,
                                      clock=clock)
    b = elastic.MembershipCoordinator(tmp_path, "b", lease_secs=5.0,
                                      clock=clock)
    a.renew()
    b.renew()
    ftb = fleet.FleetTelemetry(tmp_path, "b", every_s=0.0, clock=clock)
    ftb.record_step(4, mesh_epoch=1)
    t[0] += 6.0                     # b's lease expires
    a.renew()
    assert a.evict_expired() == ["b"]
    bundles = list((tmp_path / "postmortem").glob("b.evicted.*.json"))
    assert len(bundles) == 1
    bundle = json.loads(bundles[0].read_text())
    assert bundle["host"] == "b" and bundle["recorded_by"] == "a"
    assert bundle["final_telemetry"]["step"] == 4


# =========================================================================
# elastic hooks: barrier stamps through ElasticContext + trainer dump
# =========================================================================

def test_elastic_context_stamps_barriers_and_publishes(tmp_path):
    t, clock = _clockpair()
    co = elastic.MembershipCoordinator(tmp_path, "a", lease_secs=5.0,
                                       clock=clock, port_base=31000)
    co.renew()
    ft = fleet.FleetTelemetry(tmp_path, "a", every_s=0.0, clock=clock)
    ctx = elastic.ElasticContext(co, {"epoch": 0, "members": ["a"],
                                      "port": 1}, fleet=ft)
    ctx.pre_step(0)                 # barrier entry at t=1000
    t[0] += 0.5
    ctx.post_step(0, 0.25)          # barrier exit at t=1000.5
    snap = json.loads((tmp_path / "telemetry" / "a.json").read_text())
    (b,) = snap["barriers"]
    assert b == [0, 1000.0, 1000.5]
    assert snap["mesh_epoch"] == 0
    # a context with NO fleet plane: both hooks are one branch
    ctx2 = elastic.ElasticContext(co, {"epoch": 0, "members": ["a"],
                                       "port": 1})
    p0 = fleet.publishes()
    ctx2.pre_step(1)
    ctx2.post_step(1, 0.1)
    assert fleet.publishes() == p0


def test_elastic_trainer_dumps_flight_bundle_on_nonfinite(tmp_path):
    """A deterministic failure (the numerics sentinel) surfaces AND
    leaves the postmortem bundle behind — the black box survives the
    failure it explains."""
    from deeplearning4j_tpu.obs.numerics import NonFiniteError
    co = elastic.MembershipCoordinator(tmp_path / "el", "solo",
                                       lease_secs=5.0,
                                       port_base=31800)
    tr = elastic.ElasticTrainer(
        _mlp, tmp_path / "ck", coordinator=co, sharded_update=False,
        save_every=0, fleet_telemetry=True)
    with faults.active("worker_step:error=NonFiniteError:nth=2"):
        with pytest.raises(NonFiniteError):
            tr.fit(_iter(), epochs=1, expected=1)
    co.stop_auto_renew()
    bundles = list((tmp_path / "el" / "postmortem").glob("*.json"))
    assert len(bundles) == 1
    bundle = json.loads(bundles[0].read_text())
    assert bundle["cause"] == "NonFiniteError"
    assert bundle["host"] == "solo"
    # the ring captured the step that preceded the failure
    steps = [r["step"] for r in bundle["ring"] if "step" in r]
    assert steps and steps[-1] >= 0


# =========================================================================
# heartbeat-plane unification: one staleness table
# =========================================================================

def test_healthz_names_stale_hosts_and_workers_from_one_table():
    health.reset()
    try:
        health.heartbeat("w-live")
        health.heartbeat("w-stuck", t=obs.now() - 100)   # > default 30
        # a host 10s silent under a 5s lease: stale by ITS window even
        # though the generic worker default (30s) would say ok — the
        # unified table renders the coordinator's verdict
        health.observe_age("host:hX", 10.0, stale_after=5.0)
        chk = health.check()
        assert chk["host:hX"]["stale"] is True
        assert chk["w-live"]["stale"] is False
        body = metrics.MetricsServer(port=0).healthz()
        assert body["status"] == "stale_workers"
        assert body["stale_workers"] == ["host:hX", "w-stuck"]
        assert body["stale_hosts"] == ["hX"]
    finally:
        health.reset()


def test_observe_age_threshold_cleared_on_retire():
    health.reset()
    try:
        health.observe_age("host:gone", 1.0, stale_after=5.0)
        health.retire("host:gone")
        assert health.check() == {}
        # re-registering without an override falls back to the default
        health.heartbeat("host:gone", t=obs.now() - 10.0)
        assert health.check(stale_after=30.0)["host:gone"][
            "stale"] is False
    finally:
        health.reset()


# =========================================================================
# the off path: zero publishes, zero dumps, one branch
# =========================================================================

def test_off_path_zero_publish_counter_fence():
    """Training with NO fleet plane installed must never touch the
    publisher or the recorder — the PR 2/4 off-path contract."""
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    p0, d0 = fleet.publishes(), fleet.dumps()
    fam0 = fleet.FLEET_PUBLISHES._children[()].get()
    net = _mlp()
    ParallelWrapper(net, workers=2, prefetch_buffer=0).fit(
        _iter(n=16, batch=8), epochs=1)
    net2 = _mlp()
    net2.fit(_iter(n=16, batch=8), epochs=1)
    assert fleet.publishes() == p0
    assert fleet.dumps() == d0
    assert fleet.FLEET_PUBLISHES._children[()].get() == fam0


def test_measure_publish_overhead_scrubs_probe_counters():
    p0 = fleet.publishes()
    fam0 = fleet.FLEET_PUBLISHES._children[()].get()
    rec = fleet.measure_publish_overhead(step_seconds=0.05, iters=200)
    assert rec["publishes"] >= 1            # the probe did publish...
    assert fleet.publishes() == p0          # ...and scrubbed itself
    assert fleet.FLEET_PUBLISHES._children[()].get() == fam0
    assert rec["off_path_cost_us"] < rec["on_path_record_us"] + 1e3
    assert rec["overhead_pct_of_step"] is not None


# =========================================================================
# tpu_watch --fleet-dir: table + skew sparkline + alarms
# =========================================================================

def test_tpu_watch_fleet_dir_renders_view(tmp_path, capsys):
    from deeplearning4j_tpu.obs import numerics
    sys.path.insert(0, str(REPO / "tools"))
    import tpu_watch
    eldir = tmp_path / "el"
    base = time.time()
    nf = numerics.NONFINITE.labels(layer="dense_0", kind="gradients")
    nf.inc()
    try:
        for host, late in (("h0", 0.0), ("h1", 0.03)):
            ft = fleet.FleetTelemetry(eldir, host, every_s=0.0)
            ft.record_step(9, mesh_epoch=2, t_enter=base + late,
                           t_exit=base + late + 0.01, loss=0.4)
        dead = fleet.FleetTelemetry(eldir, "h2", every_s=0.0)
        dead.record_step(7, mesh_epoch=1)
        fleet.record_eviction(eldir, "h2", by="h0")
        tpu_watch._scrape_telemetry(None, None, None,
                                    fleet_dir=str(eldir))
    finally:
        # scrub the synthetic non-finite sample from the live registry
        with numerics.NONFINITE._lock:
            numerics.NONFINITE._children.pop(
                ("dense_0", "gradients"), None)
    recs = [json.loads(ln) for ln in
            capsys.readouterr().out.splitlines() if ln.startswith("{")]
    (rec,) = [r for r in recs if r["event"] == "fleet"]
    assert set(rec["hosts"]) == {"h0", "h1"}
    assert rec["hosts"]["h0"]["step"] == 9
    assert rec["hosts"]["h0"]["mesh_epoch"] == 2
    assert rec["skew"]["straggler"] == "h1"
    assert rec["skew"]["max_skew_s"] == pytest.approx(0.03, abs=1e-4)
    assert rec["skew"]["sparkline"]
    assert rec["skew"]["series"][-1][2] == "h1"   # last-in, by step
    assert rec["alarms"]["EVICTED"] == ["h2"]
    assert any("dense_0/gradients" in k
               for k in rec["alarms"]["NONFINITE"])


# =========================================================================
# FAMILIES registry sanity (the in-process complement to lint rule 6)
# =========================================================================

def test_every_live_family_is_declared_in_families_table():
    reg_names = set(metrics.REGISTRY._metrics)
    for name, kind, _doc, _samples in metrics.REGISTRY._collected():
        reg_names.add(name)
    undeclared = {n for n in reg_names if n.startswith("dl4j_tpu_")} \
        - set(metrics.FAMILIES)
    assert not undeclared, undeclared


# =========================================================================
# the 3-host drill: publish → aggregate → kill → postmortem
# =========================================================================

FLEET_WORKER = r"""
import json, os, signal, sys, time
sys.path.insert(0, __REPO__)
from deeplearning4j_tpu.obs import fleet, metrics
from deeplearning4j_tpu.resilience import elastic

pid = os.environ["PROC_ID"]
host = "h" + pid
d = os.environ["ELASTIC_DIR"]
lease = float(os.environ["LEASE_S"])
STEPS = int(os.environ["STEPS"])
KILL_AT = int(os.environ["KILL_AT"])
victim = os.environ.get("KILL_HOST", "") == pid

co = elastic.MembershipCoordinator(d, host, lease_secs=lease,
                                   port_base=31900)
co.renew()
ft = fleet.FleetTelemetry(d, host, every_s=0.0)
for i in range(STEPS):
    t0 = time.time()
    metrics.STEPS.labels(entry="fleet_drill").inc()
    time.sleep(0.02)
    ft.record_step(i, mesh_epoch=1, t_enter=t0, loss=1.0 / (i + 1))
    co.maybe_renew()
    if pid == "0" and i == KILL_AT // 2:
        # all three hosts live: the aggregate view must carry every
        # host's samples and parse as valid exposition
        deadline = time.time() + 20
        while len(fleet.read_snapshots(d)) < 3 and \
                time.time() < deadline:
            time.sleep(0.05)
        view = fleet.aggregate(d)
        fams = metrics.parse_exposition(view.exposition())
        hosts = sorted({dict(l).get("host") for _n, l in fams
                        if dict(l).get("host")})
        print("AGG hosts=%d names=%s" % (len(view.table()),
                                         ",".join(hosts)), flush=True)
    if victim and i == KILL_AT:
        os.kill(os.getpid(), signal.SIGKILL)

# survivors: let the victim's lease expire (renewing our own), name
# the straggler from the aggregate, then evict — the winner of the
# lease race snapshots the corpse's final telemetry into the bundle.
# h1 waits for h0's straggler verdict before evicting, so the corpse's
# snapshot is still live when the skew report ranks it
marker = os.path.join(d, "straggler.done")
for _ in range(int(lease / 0.2) + 4):
    co.renew()
    time.sleep(0.2)
if pid == "0":
    rep = fleet.aggregate(d).skew_report()
    print("STRAGGLER=%s missing=%s" % (rep["straggler"],
                                       ",".join(rep["missing"])),
          flush=True)
    with open(marker, "w") as f:
        f.write("done")
else:
    deadline = time.time() + 30
    while not os.path.exists(marker) and time.time() < deadline:
        co.renew()
        time.sleep(0.1)
deadline = time.time() + 30
bundle = None
while time.time() < deadline:
    co.renew()
    co.evict_expired()
    found = list((__import__("pathlib").Path(d) / "postmortem")
                 .glob("h*.evicted.*.json")) \
        if os.path.isdir(os.path.join(d, "postmortem")) else []
    if found:
        bundle = found[0]
        break
    time.sleep(0.2)
print("proc %s DONE bundle=%s" % (pid, bundle), flush=True)
"""


@pytest.mark.skipif(os.environ.get("DL4J_TPU_SKIP_MP") == "1",
                    reason="multi-process test disabled")
def test_three_hosts_publish_aggregate_and_postmortem(tmp_path):
    """ISSUE 12 satellite: 3 hosts publish, the aggregate exposition
    carries host= labels and parses; SIGKILL one host → the skew view
    names it the straggler, and the surviving leader's postmortem
    bundle exists, parses, and names the dead host and its last
    step."""
    sys.path.insert(0, str(REPO / "tests"))
    from mp_harness import run_workers

    script = tmp_path / "fleet_worker.py"
    script.write_text(FLEET_WORKER.replace("__REPO__",
                                           repr(str(REPO))))
    eldir = tmp_path / "elastic"
    kill_at = 12
    env = {"ELASTIC_DIR": str(eldir), "LEASE_S": "1.5",
           "STEPS": "24", "KILL_AT": str(kill_at), "KILL_HOST": "2"}
    procs, outs = run_workers(script, port=29990, n=3, timeout=180,
                              kill_after={2: 60.0}, extra_env=env)
    assert procs[2].returncode == -9, outs[2][-2000:]
    for i in (0, 1):
        assert procs[i].returncode == 0, outs[i][-2000:]
        assert f"proc {i} DONE" in outs[i]
    # all three hosts were aggregated while alive
    assert "AGG hosts=3 names=h0,h1,h2" in outs[0]
    # the corpse named as straggler (missing from the newest step,
    # ranked by lease age)
    assert "STRAGGLER=h2" in outs[0] and "missing=h2" in outs[0]
    # the leader bundle: exists, parses, names the dead host and its
    # last published step
    bundles = list((eldir / "postmortem").glob("h2.evicted.*.json"))
    assert len(bundles) == 1
    bundle = json.loads(bundles[0].read_text())
    assert bundle["host"] == "h2" and bundle["cause"] == "Evicted"
    assert bundle["final_telemetry"]["step"] == kill_at
    assert bundle["final_telemetry"]["version"] == \
        fleet.SNAPSHOT_VERSION
    # eviction-time adjudication: the corpse — lease-less while its
    # snapshot was still live — is the final-step straggler
    assert bundle["fleet"]["skew"]["straggler"] == "h2"
    assert "h2" in bundle["fleet"]["skew"]["missing"]
    # post-eviction fleet view: survivors only, eviction visible
    view = fleet.aggregate(eldir)
    assert set(view.table()) == {"h0", "h1"}
    assert view.evicted() == ["h2"]
    metrics.parse_exposition(view.exposition())

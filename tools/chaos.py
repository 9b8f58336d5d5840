"""Chaos harness — run training/serving under a named fault plan and
assert it converges to the fault-free baseline.

The resilience subsystem's claim is "robust by construction, verified
by injected faults" (ARCHITECTURE.md §10); this tool IS the
verification loop, runnable from a shell and wired into tier-1 by
``tests/test_chaos_smoke.py``:

    python tools/chaos.py --plan ckpt-io-flake
    python tools/chaos.py --plan worker-crash --plan etl-flake
    python tools/chaos.py --plan serving-crash
    python tools/chaos.py --plan "ckpt_write:error=OSError:nth=1" --example lenet_mnist
    python tools/chaos.py --list

Default (builtin scenario): train one seeded MLP twice — uninterrupted
baseline, then a fresh identical net under the fault plan with
``FaultTolerantTrainer`` absorbing the injected failures — and assert
the chaotic run's final params/loss match the baseline (exact-resume
property: restore + mid-epoch skip + per-iteration rng folds replay
the same trajectory). Serving plans flood a ``ParallelInference``
queue instead and assert requests shed (fast errors) rather than
block, with the worker surviving its injected crash.

``--example NAME`` runs ``examples/NAME.py`` as a subprocess with the
plan in ``DL4J_TPU_FAULT_PLAN`` under a restart supervisor (the
slice-restart idiom: a crashed process is simply re-run, max
``--restarts`` times) and asserts eventual completion.

Exit status 0 = all assertions held; JSON report on stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

# CPU-only by nature: the scenarios start several processes (workers
# that are killed and re-formed), and a chip belongs to one process at
# a time — so the drills run on the CPU backend, parent and children
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402


def _build_net(seed=11):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Adam(learning_rate=5e-3)).list()
            .layer(DenseLayer(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(8)).build())
    return MultiLayerNetwork(conf).init()


def _data(n=96, seed=5):
    from deeplearning4j_tpu.data.dataset import DataSet
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)]
    return DataSet(x, y)


def _train_scenario(plan_name: str, epochs: int, tol: float) -> dict:
    """Baseline vs chaotic FaultTolerantTrainer run; convergence-to-
    baseline means the recovered trajectory reproduces the
    uninterrupted one (params within ``tol``)."""
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.train.fault_tolerance import (
        FaultTolerantTrainer)
    from deeplearning4j_tpu.obs import metrics

    ds = _data()
    it = ListDataSetIterator([b for b in ds.batch_by(24)], batch_size=24)

    base = _build_net()
    base.fit(it, epochs=epochs)
    base_loss = float(base.score(ds))

    chaotic = _build_net()
    preempted = False
    with tempfile.TemporaryDirectory(prefix="chaos_ckpt_") as d:
        trainer = FaultTolerantTrainer(chaotic, d,
                                       save_every_n_iterations=2,
                                       max_restarts=8)
        t0 = time.perf_counter()
        with faults.active(plan_name):
            trainer.fit(it, epochs=epochs)
            fired = sum(s["fires"] for s in faults.stats().values())
        if trainer.preempted:
            # the preempt plan stops the "job" cleanly mid-run; model
            # the slice restart: a fresh process resumes from the
            # checkpoint dir and finishes the epoch budget
            preempted = True
            from deeplearning4j_tpu.train.fault_tolerance import \
                resume_or_init
            chaotic = resume_or_init(_build_net, d)
            FaultTolerantTrainer(
                chaotic, d, save_every_n_iterations=2,
                max_restarts=8).fit(it, epochs=epochs - chaotic.epoch)
        wall = time.perf_counter() - t0
    chaos_loss = float(chaotic.score(ds))
    max_dp = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(base.params),
                                 jax.tree.leaves(chaotic.params)))
    quarantined = metrics.CKPT_QUARANTINED._children[()].get()
    ok = (fired > 0 and np.isfinite(chaos_loss)
          and abs(chaos_loss - base_loss) <= tol)
    return {"mode": "train", "plan": plan_name,
            "faults_fired": fired, "restarts": trainer.restarts,
            "preempted": preempted,
            "baseline_loss": round(base_loss, 6),
            "chaos_loss": round(chaos_loss, 6),
            "max_param_delta": max_dp,
            "exact_resume": max_dp < 1e-5,
            "quarantined": quarantined,
            "wall_s": round(wall, 2), "ok": bool(ok)}


def _serving_scenario(plan_name: str) -> dict:
    """Flood a bounded serving queue under the plan: requests must shed
    (fast QueueFullError) or complete — never block — and the dispatch
    worker must survive its injected crash."""
    from deeplearning4j_tpu.parallel.inference import (
        ParallelInference, QueueFullError)
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.obs import metrics

    net = _build_net()
    pi = ParallelInference(net, batch_limit=8, queue_limit=8,
                           buckets=(1, 2, 4, 8))
    x = np.random.RandomState(0).randn(64, 8).astype(np.float32)
    shed, failed, okc = 0, 0, 0
    t0 = time.perf_counter()
    with faults.active(plan_name):
        # phase 1 — overload burst: the bounded queue must shed (fast
        # QueueFullError) instead of blocking the submitter
        burst = []
        for i in range(32):
            try:
                burst.append(pi.output_async(x[i], deadline_s=10.0))
            except QueueFullError:
                shed += 1
        # phase 2 — paced waves (submit, then gather, so the worker
        # forms several batches): the injected crash takes one whole
        # batch (those requests get the error immediately), later
        # waves are served by the SAME worker thread — it recovered,
        # not died
        for ob in burst:
            try:
                ob.get(timeout=10.0)
                okc += 1
            except Exception:
                failed += 1
        for _ in range(4):
            wave = [pi.output_async(x[j], deadline_s=10.0)
                    for j in range(4)]
            for ob in wave:
                try:
                    ob.get(timeout=10.0)
                    okc += 1
                except Exception:
                    failed += 1
        fired = sum(s["fires"] for s in faults.stats().values())
    # the worker survived the injected batch failure: a fresh request
    # still round-trips
    post = np.asarray(pi.output(x[0], timeout=10.0))
    pi.shutdown()
    wall = time.perf_counter() - t0
    total = 32 + 4 * 4
    shed_total = sum(
        c.get() for c in metrics.REQS_SHED._children.values())
    ok = (fired > 0 and okc > 0 and failed > 0 and shed > 0
          and post.shape[-1] == 3 and okc + failed + shed == total
          and wall < 30.0)
    return {"mode": "serving", "plan": plan_name, "requests": total,
            "completed": okc, "errored_by_fault": failed,
            "shed_at_enqueue": shed, "shed_metric_total": shed_total,
            "faults_fired": fired, "worker_survived": True,
            "wall_s": round(wall, 2), "ok": bool(ok)}


def _gateway_scenario(plan_name: str) -> dict:
    """Continuous-batching gateway under an injected serving fault
    (ISSUE 13 satellite): the fault takes one decode iteration
    mid-trace — every in-flight sequence must shed with a structured
    ``SequenceAborted`` (tokens-so-far attached) or complete, the
    paged pool must come back whole (no leaked page, invariants
    clean), and the SAME worker must serve a post-fault wave — never
    a wedged slot. The drill runs under an obs trace so the Chrome
    JSONL carries the REQUEST-SCOPED spans (submit → admit → prefill
    → decode-steps → retire/abort, async tracks keyed by request id)
    — asserted here: every submitted request must leave a terminal
    ``serving.request`` span, aborts included."""
    import tempfile

    from deeplearning4j_tpu.obs import metrics, trace as obs_trace
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.serving import SequenceAborted, ServingGateway
    from deeplearning4j_tpu.zoo import GPTNano

    model = GPTNano(vocab_size=64, max_len=64, seed=7)
    net = model.init()
    gw = ServingGateway(model, net, max_slots=4, block=8,
                        max_context=64, queue_limit=32,
                        default_max_new=24)
    gw.warmup(prompt_lens=(6,))
    rng = np.random.RandomState(0)
    prompts = rng.randint(0, 64, (8, 6)).astype(np.int32)
    completed, aborted, tokens_salvaged = 0, 0, 0
    # reuse a live user trace (enable() would close and redirect it);
    # otherwise trace into a drill-local file and tear down after
    trace_was_on = obs_trace.enabled() and obs_trace.trace_path()
    if trace_was_on:
        trace_path = obs_trace.trace_path()
        started_trace = False
    else:
        trace_path = tempfile.mktemp(prefix="dl4j_gateway_drill_",
                                     suffix=".jsonl")
        obs_trace.enable(trace_path)
        started_trace = True
    t0 = time.perf_counter()
    try:
        with faults.active(plan_name):
            wave = [gw.submit(p) for p in prompts]
            for ob in wave:
                try:
                    ob.result(timeout=60)
                    completed += 1
                except SequenceAborted as e:
                    aborted += 1
                    tokens_salvaged += len(e.tokens)
            fired = sum(s["fires"] for s in faults.stats().values())
        # the worker survived: a post-fault wave round-trips on the
        # same gateway, and the pool is conserved
        post = [gw.submit(p, max_new=8) for p in prompts[:3]]
        post_ok = sum(ob.result(timeout=60).shape == (14,)
                      for ob in post)
    finally:
        obs_trace.flush()
        if started_trace:
            obs_trace.disable()
    gw._sched.pager.check_invariants()
    pages_whole = (gw._sched.pager.free_pages()
                   == gw._sched.pager.n_pages - 1)
    shed_fault = metrics.SERVING_SHED.labels(reason="fault").get()
    gw.shutdown()
    wall = time.perf_counter() - t0
    # request-scoped span fence: 11 submits -> 11 terminal request
    # tracks (retired or aborted), nested decode phases present (>=
    # when riding a pre-existing user trace with earlier traffic)
    evs = obs_trace.read_trace(trace_path)
    req_begins = [e for e in evs if e.get("ph") == "b"
                  and e.get("name") == "serving.request"]
    phases = {e.get("name") for e in evs
              if e.get("ph") == "b"
              and str(e.get("name", "")).startswith("serving.request")}
    outcomes = [e["args"].get("outcome") for e in req_begins
                if "args" in e]
    spans_ok = (len(req_begins) >= 11
                and {"serving.request",
                     "serving.request/queue_wait",
                     "serving.request/prefill",
                     "serving.request/decode_steps"} <= phases
                and any(o.startswith("aborted") for o in outcomes)
                and any(o == "retired" for o in outcomes))
    ok = (fired > 0 and aborted > 0 and completed + aborted == 8
          and tokens_salvaged > 0 and post_ok == 3 and pages_whole
          and spans_ok and wall < 60.0)
    return {"mode": "serving-gateway", "plan": plan_name,
            "requests": 8, "completed": completed, "aborted": aborted,
            "tokens_salvaged": tokens_salvaged,
            "post_fault_completed": post_ok,
            "pages_conserved": pages_whole,
            "shed_fault_metric": shed_fault, "faults_fired": fired,
            "worker_survived": True,
            "request_spans": len(req_begins),
            "request_span_phases": sorted(phases),
            "trace_jsonl": trace_path,
            "wall_s": round(wall, 2), "ok": bool(ok)}


def _gateway_cow_scenario(plan_name: str) -> dict:
    """Gateway drill under copy-on-write prefix sharing + speculative
    decode (ISSUE 16 satellite): a serving-site fault takes a decode
    iteration while sibling sequences share refcounted pages. The
    fence: aborted sequences release only their OWN refs (the donor
    retiring early must not free pages its siblings still read, and a
    mid-flight shed must not leak or double-free a shared page), the
    pool comes back conserved with invariants clean, and the same
    worker then serves a fresh shared wave whose outputs match the
    dense ``generate()`` token-for-token."""
    from deeplearning4j_tpu.obs import metrics
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.serving import SequenceAborted, ServingGateway
    from deeplearning4j_tpu.zoo import GPTNano

    model = GPTNano(vocab_size=64, max_len=64, seed=7)
    net = model.init()
    gw = ServingGateway(model, net, max_slots=4, block=8,
                        max_context=64, queue_limit=32,
                        default_max_new=24, spec_k=2,
                        prefix_sharing=True)
    # every prompt in the drill is the 12-token base (bucket 16) and
    # the suffix warmup closes downward on its own — more admit
    # buckets would only add fresh-model compile time to the smoke
    gw.warmup(prompt_lens=(12,))
    rng = np.random.RandomState(3)
    base = rng.randint(0, 64, 12).astype(np.int32)
    hits0 = metrics.SERVING_PREFIX_HITS.snapshot().get("", 0)
    cow0 = metrics.SERVING_PREFIX_COW.snapshot().get("", 0)
    completed = aborted = 0
    t0 = time.perf_counter()
    with faults.active(plan_name):
        # park the worker so the whole wave admits in ONE sweep: the
        # donor registers the prefix chain and every sibling adopts
        # its pages (tail CoW) before the first — faultable — step
        gw.pause()
        wave = [gw.submit(base, max_new=2)]          # donor: retires
        wave += [gw.submit(base, max_new=24)          # early, sharers
                 for _ in range(3)]                   # decode on
        gw.resume()
        for ob in wave:
            try:
                ob.result(timeout=60)
                completed += 1
            except SequenceAborted:
                aborted += 1
        fired = sum(s["fires"] for s in faults.stats().values())
    gw._sched.pager.check_invariants()
    pages_whole = (gw._sched.pager.free_pages()
                   == gw._sched.pager.n_pages - 1)
    # post-fault: same worker, fresh shared wave, dense-identical out
    dense = np.asarray(model.generate(net, base[None], n_new=8))[0]
    gw.pause()
    post = [gw.submit(base, max_new=8) for _ in range(3)]
    gw.resume()
    post_ok = sum(
        bool(np.array_equal(np.asarray(ob.result(timeout=60)), dense))
        for ob in post)
    gw._sched.pager.check_invariants()
    pages_whole &= (gw._sched.pager.free_pages()
                    == gw._sched.pager.n_pages - 1)
    hits = metrics.SERVING_PREFIX_HITS.snapshot().get("", 0) - hits0
    cows = metrics.SERVING_PREFIX_COW.snapshot().get("", 0) - cow0
    gw.shutdown()
    wall = time.perf_counter() - t0
    # 3 wave siblings + >=2 post siblings adopt the donor chain; each
    # whole-prompt adoption clones the tail page before writing it
    ok = (fired > 0 and aborted > 0 and completed + aborted == 4
          and post_ok == 3 and pages_whole and hits >= 5
          and cows >= 3 and wall < 60.0)
    return {"mode": "serving-gateway-cow", "plan": plan_name,
            "requests": 4, "completed": completed, "aborted": aborted,
            "post_fault_dense_identical": post_ok,
            "pages_conserved": pages_whole,
            "prefix_hits": int(hits), "cow_copies": int(cows),
            "faults_fired": fired, "worker_survived": True,
            "wall_s": round(wall, 2), "ok": bool(ok)}


# ---------------------------------------------------------------------------
# elastic multi-host drill (resilience/elastic.py on tests/mp_harness.py)
# ---------------------------------------------------------------------------

# One elastic host: join the fleet, form the mesh at the agreed world
# size, reshard-restore the newest valid sharded checkpoint, train
# under bounded-timeout collectives, re-form by exec on peer death.
# The victim host (PROC_ID == KILL_HOST) SIGKILLs itself at iteration
# KILL_AT — a real kill -9 mid-epoch, deterministic where a wall-clock
# kill is not (the parent's mp_harness kill_after stays armed as the
# backstop for a pre-step wedge).
ELASTIC_WORKER = textwrap.dedent("""
    import os, signal, sys, warnings
    sys.path.insert(0, %(repo)r)
    warnings.filterwarnings("ignore")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import hashlib
    import numpy as np

    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.resilience import elastic

    host = "h%%s" %% os.environ["PROC_ID"]
    EPOCHS = int(os.environ["EPOCHS"])
    LEASE = float(os.environ["LEASE_S"])
    BASELINE_STEP = int(os.environ.get("BASELINE_STEP", "0"))
    SAVE_EVERY = int(os.environ.get("SAVE_EVERY", "2"))
    KILL_AT = int(os.environ.get("KILL_AT", "0"))
    victim = os.environ.get("KILL_HOST", "") == os.environ["PROC_ID"]

    def factory():
        conf = (NeuralNetConfiguration.builder().seed(23)
                .updater(upd.Adam(learning_rate=2e-3)).list()
                .layer(DenseLayer(n_out=18, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(10)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(5)          # same data on every host
    x = rng.standard_normal((32, 10)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
    it = ListDataSetIterator(DataSet(x, y), batch_size=8)  # 4/epoch

    co = elastic.MembershipCoordinator(
        os.environ["ELASTIC_DIR"], host, lease_secs=LEASE,
        port_base=int(os.environ["PORT_BASE"]))
    tr = elastic.ElasticTrainer(
        factory, os.environ["CKPT_DIR"], coordinator=co,
        save_every=SAVE_EVERY, keep_last=50)
    wrapper, rec = tr.bring_up(expected=int(os.environ["NPROC"]))
    net = tr.net
    print("%%s WORLD=%%d EPOCH=%%d DEV=%%d" %% (
        host, len(rec["members"]), rec["epoch"],
        len(jax.devices())), flush=True)
    if tr.resumed_step is not None:
        print("%%s RESUMED step=%%d" %% (host, tr.resumed_step),
              flush=True)
    if BASELINE_STEP:
        # same-scale uninterrupted baseline: pin the restore to the
        # exact step the survivors resumed from
        tr._ck.restore_wrapper(wrapper, step=BASELINE_STEP)
        print("%%s PINNED step=%%d" %% (host, BASELINE_STEP),
              flush=True)

    if victim and KILL_AT:
        class Killer:
            def iteration_done(self, _net, iteration, _epoch):
                if iteration >= KILL_AT:
                    print("%%s SELF-SIGKILL at iter %%d" %% (
                        host, iteration), flush=True)
                    os.kill(os.getpid(), signal.SIGKILL)
            def on_epoch_start(self, _net):
                pass
            def on_epoch_end(self, _net):
                pass
        net.listeners.append(Killer())

    status = tr.fit(it, epochs=EPOCHS)       # execs on peer death
    digest = hashlib.sha1()
    for leaf in jax.tree_util.tree_leaves(net.params):
        digest.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    print("%%s FINAL status=%%s iter=%%d epoch=%%d loss=%%.6f "
          "checksum=%%s" %% (host, status, net.iteration, net.epoch,
                             net.score_, digest.hexdigest()),
          flush=True)
    from deeplearning4j_tpu.obs import metrics as M
    for line in M.exposition().splitlines():
        if line.startswith(("dl4j_tpu_mesh_epoch",
                            "dl4j_tpu_hosts_evicted_total",
                            "dl4j_tpu_resilience_restarts_total",
                            "dl4j_tpu_preemptions_total")):
            print("%%s METRIC %%s" %% (host, line), flush=True)
    print("proc %%s DONE" %% os.environ["PROC_ID"], flush=True)
    # skip the interpreter's atexit distributed-shutdown barrier: a
    # host that departs (preempted) or finishes while a peer is dead
    # would wedge or abort inside it — the work is done, leave hard
    sys.stdout.flush()
    os._exit(0)
""")


def _elastic_scenario(hosts: int = 3, kill_host: int = 2,
                      kill_at_iter: int = 9, epochs: int = 8,
                      lease_s: float = 3.0, port: int = 0) -> dict:
    """The multi-host chaos drill (acceptance fence of ISSUE 7):
    SIGKILL one host of an ``hosts``-process fleet mid-epoch, assert
    the survivors (a) raise out of the dead collective within the
    lease window, (b) re-form the mesh at the reduced world size with
    a bumped mesh epoch, (c) reshard-restore the newest valid sharded
    checkpoint, and (d) reach a final state bit-identical to a
    same-scale uninterrupted baseline resumed from the same step.
    (Graceful SIGTERM departure is the sibling drill,
    :func:`_elastic_preempt_scenario`.)"""
    import re
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tests"))
    from mp_harness import run_workers

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = port or 30200 + (os.getpid() % 300)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chaos_elastic_") as d:
        script = os.path.join(d, "elastic_worker.py")
        with open(script, "w") as f:
            f.write(ELASTIC_WORKER % {"repo": repo})
        ckdir = os.path.join(d, "ckpt")
        env = {"ELASTIC_DIR": os.path.join(d, "elastic"),
               "CKPT_DIR": ckdir, "EPOCHS": str(epochs),
               "LEASE_S": str(lease_s), "PORT_BASE": str(port + 50),
               "KILL_HOST": str(kill_host), "SAVE_EVERY": "2",
               "KILL_AT": str(kill_at_iter),
               # fleet plane at a fast cadence so the victim's final
               # telemetry is fresh when the leader snapshots it
               "DL4J_TPU_FLEET_PUBLISH_SECS": "0.05"}
        # mp_harness kill_after is the BACKSTOP (a host wedged before
        # its self-kill iteration still dies); the deterministic kill
        # is the victim's in-worker SIGKILL at iteration KILL_AT
        procs, outs = run_workers(
            script, port, n=hosts, timeout=420,
            kill_after={kill_host: 90.0},
            extra_env=env)

        survivors = [i for i in range(hosts) if i != kill_host]
        victim_rc = procs[kill_host].returncode
        ok = victim_rc == -9        # a real SIGKILL took the host
        finals = {}
        resumed = None
        detect_s = None
        mesh_epoch = None
        world = None
        evicted = 0
        restarts = 0
        for i in survivors:
            out = outs[i]
            ok = ok and procs[i].returncode == 0 and \
                f"proc {i} DONE" in out
            m = re.findall(r"WORLD=(\d+) EPOCH=(\d+)", out)
            if m:
                world, mesh_epoch = int(m[-1][0]), int(m[-1][1])
            r = re.search(r"RESUMED step=(\d+)", out)
            if r:
                resumed = int(r.group(1))
            dm = re.search(r"ELASTIC_REFORM .*detect_s=([\d.]+)", out)
            if dm:
                detect_s = float(dm.group(1))
            fm = re.search(r"FINAL .*checksum=([0-9a-f]+)", out)
            if fm:
                finals[i] = fm.group(1)
            em = re.search(
                r"dl4j_tpu_hosts_evicted_total (\d+)", out)
            if em:
                evicted = max(evicted, int(em.group(1)))
            rm = re.search(
                r"dl4j_tpu_resilience_restarts_total (\d+)", out)
            if rm:
                restarts = max(restarts, int(rm.group(1)))
        ok = (ok and len(finals) == len(survivors)
              and len(set(finals.values())) == 1
              and resumed is not None and resumed > 0
              and world == hosts - 1 and mesh_epoch == 2
              and detect_s is not None and detect_s <= 4 * lease_s
              and evicted >= 1 and restarts >= 1)

        # fleet observability plane (obs/fleet.py): the drill doubles
        # as the acceptance fence for the flight recorder + fleet
        # exposition — (a) a survivor's postmortem bundle must exist
        # whose skew series names the killed host as the final-step
        # straggler, (b) the surviving leader's eviction bundle must
        # carry the corpse's final telemetry (host + last step), and
        # (c) the post-reform fleet exposition must carry
        # mesh_epoch="2" labels
        import glob

        from deeplearning4j_tpu.obs import fleet as obs_fleet
        from deeplearning4j_tpu.obs import metrics as obs_metrics
        victim = f"h{kill_host}"
        pm = sorted(glob.glob(os.path.join(env["ELASTIC_DIR"],
                                           "postmortem", "*.json")))
        straggler_final = None
        survivor_bundles = 0
        evicted_named = False
        dead_last_step = None
        for b in pm:
            try:
                with open(b) as f:
                    rec = json.load(f)
            except ValueError:
                continue
            if rec.get("cause") == "Evicted" and \
                    rec.get("host") == victim:
                evicted_named = True
                dead_last_step = (rec.get("final_telemetry")
                                  or {}).get("step")
                # the ADJUDICATED final-step straggler: the eviction
                # bundle's skew view is computed after the lease
                # verdict, so it names the corpse deterministically
                # (survivor dumps race instant transport errors and
                # are best-effort testimony)
                straggler_final = ((rec.get("fleet") or {})
                                   .get("skew") or {}).get("straggler")
            elif rec.get("host") != victim and \
                    ((rec.get("fleet") or {}).get("skew") or {}
                     ).get("straggler"):
                survivor_bundles += 1
        view = obs_fleet.aggregate(env["ELASTIC_DIR"])
        fams = obs_metrics.parse_exposition(view.exposition())
        expo_epochs = sorted({dict(labels).get("mesh_epoch")
                              for _n, labels in fams
                              if "mesh_epoch" in dict(labels)})
        fleet_epoch2 = "2" in expo_epochs
        ok = (ok and straggler_final == victim and evicted_named
              and survivor_bundles >= 1
              and dead_last_step is not None and dead_last_step > 0
              and fleet_epoch2)

        # same-scale uninterrupted baseline: fresh fleet of the
        # surviving size, pinned to the exact step the survivors
        # resumed from, trained to the same epoch budget — the
        # post-recovery trajectory must match it bit-for-bit
        base_env = dict(env, ELASTIC_DIR=os.path.join(d, "el_base"),
                        BASELINE_STEP=str(resumed or 0),
                        SAVE_EVERY="0", KILL_AT="0", KILL_HOST="")
        base_env["PORT_BASE"] = str(port + 150)
        bprocs, bouts = run_workers(script, port + 100,
                                    n=hosts - 1, timeout=420,
                                    extra_env=base_env)
        base_finals = set()
        for i, out in enumerate(bouts):
            ok = ok and bprocs[i].returncode == 0
            fm = re.search(r"FINAL .*checksum=([0-9a-f]+)", out)
            if fm:
                base_finals.add(fm.group(1))
        trajectory_match = (len(base_finals) == 1 and len(finals) > 0
                            and base_finals == set(finals.values()))
        ok = ok and trajectory_match
        if not ok:                  # post-mortem material
            tails = {f"drill_{i}": (outs[i] or "")[-1500:]
                     for i in range(hosts)}
            tails.update({f"base_{i}": (bouts[i] or "")[-1500:]
                          for i in range(len(bouts))})
            print(json.dumps({"output_tails": tails}, indent=1),
                  file=sys.stderr)
        return {"mode": "elastic", "hosts": hosts,
                "killed": kill_host, "victim_rc": victim_rc,
                "survivor_world": world, "mesh_epoch": mesh_epoch,
                "resumed_step": resumed,
                "detect_s": detect_s, "lease_s": lease_s,
                "hosts_evicted": evicted, "restarts": restarts,
                "trajectory_match": trajectory_match,
                "flight_bundles": len(pm),
                "survivor_bundles": survivor_bundles,
                "straggler_final": straggler_final,
                "evict_bundle_named_dead": evicted_named,
                "dead_last_step": dead_last_step,
                "fleet_mesh_epochs": expo_epochs,
                "fleet_epoch2": fleet_epoch2,
                "wall_s": round(time.perf_counter() - t0, 2),
                "ok": bool(ok)}


def _elastic_preempt_scenario(hosts: int = 2,
                              plan: str = "host-preempt",
                              epochs: int = 8, lease_s: float = 3.0,
                              port: int = 0) -> dict:
    """host-preempt named-plan drill: host ``hosts-1`` trains under
    ``DL4J_TPU_FAULT_PLAN=host-preempt`` (SIGTERM at its nth elastic
    step), departs GRACEFULLY (lease dropped, no checkpoint torn),
    and the survivors re-form and finish."""
    import tempfile

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tests"))
    from mp_harness import run_workers

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = port or 30600 + (os.getpid() % 200)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chaos_preempt_") as d:
        script = os.path.join(d, "elastic_worker.py")
        with open(script, "w") as f:
            f.write(ELASTIC_WORKER % {"repo": repo})
        env = {"ELASTIC_DIR": os.path.join(d, "elastic"),
               "CKPT_DIR": os.path.join(d, "ckpt"),
               "EPOCHS": str(epochs), "LEASE_S": str(lease_s),
               "PORT_BASE": str(port + 50), "SAVE_EVERY": "2",
               "KILL_AT": "0", "KILL_HOST": ""}
        procs, outs = run_workers(
            script, port, n=hosts, timeout=420, extra_env=env,
            per_proc_env={hosts - 1: {"DL4J_TPU_FAULT_PLAN": plan}})
        victim_out = outs[hosts - 1] or ""
        ok = (procs[hosts - 1].returncode == 0
              and "status=preempted" in victim_out
              and "fault injection: firing 'sigterm' at site "
                  "'host_death'" in victim_out)
        survivor_done = 0
        for i in range(hosts - 1):
            out = outs[i] or ""
            if procs[i].returncode == 0 and "status=done" in out:
                survivor_done += 1
        ok = ok and survivor_done == hosts - 1
        res = {"mode": "elastic-preempt", "plan": plan,
               "hosts": hosts, "survivors_done": survivor_done,
               "victim_preempted": "status=preempted" in victim_out,
               "wall_s": round(time.perf_counter() - t0, 2),
               "ok": bool(ok)}
        if not ok:                  # post-mortem material
            res["output_tails"] = {
                i: (outs[i] or "")[-1500:] for i in range(hosts)}
        return res


REPLICA_WORKER = textwrap.dedent("""
    import json, os, sys, time, warnings
    sys.path.insert(0, %(repo)r)
    warnings.filterwarnings("ignore")
    import jax
    jax.config.update("jax_platforms", "cpu")

    from deeplearning4j_tpu.obs import fleet as obs_fleet
    from deeplearning4j_tpu.perf import compile_store
    from deeplearning4j_tpu.resilience.elastic import \\
        MembershipCoordinator
    from deeplearning4j_tpu.serving.fleet import ServingReplica
    from deeplearning4j_tpu.serving.gateway import ServingGateway
    from deeplearning4j_tpu.zoo import GPTNano

    host = os.environ["REPLICA_ID"]
    fleet_dir = os.environ["FLEET_DIR"]
    lease = float(os.environ["LEASE_S"])
    reports = os.path.join(fleet_dir, "reports")
    os.makedirs(reports, exist_ok=True)

    model = GPTNano(vocab_size=64, max_len=64, seed=7)
    net = model.init()
    gw = ServingGateway(model, net, max_slots=4, block=8,
                        max_context=64)
    co = MembershipCoordinator(fleet_dir, host, n_devices=1,
                               lease_secs=lease)
    tel = obs_fleet.FleetTelemetry(fleet_dir, host, every_s=0.05)
    rep = ServingReplica(gw, co, tel,
                         store=compile_store.from_env())
    t0 = time.perf_counter()
    report = rep.start(prompt_lens=(8, 16))
    report["warm_s"] = round(time.perf_counter() - t0, 3)
    report["port"] = rep.server.port
    report["cache"] = dict(rep.server.stats().get("cache") or {})
    with open(os.path.join(reports, host + ".json"), "w") as f:
        json.dump(report, f)
    print("REPLICA %%s READY port=%%d warm_s=%%.3f manifest_hit=%%s"
          %% (host, rep.server.port, report["warm_s"],
             report["manifest_hit"]), flush=True)
    stop = os.path.join(fleet_dir, "STOP")
    while not os.path.exists(stop):
        rep.tick()
        time.sleep(lease / 4.0)
    final = rep.server.stats()
    final["epoch"] = tel.mesh_epoch
    with open(os.path.join(reports, host + "_final.json"), "w") as f:
        json.dump(final, f)
    rep.stop()
    print("REPLICA %%s DONE epoch=%%d" %% (host, final["epoch"]),
          flush=True)
    sys.stdout.flush()
    os._exit(0)
""")


def _serving_fleet_scenario(replicas: int = 3, lease_s: float = 2.0,
                            trace_requests: int = 36,
                            threads: int = 6,
                            tenants: int = 4) -> dict:
    """ISSUE 18 acceptance drill (``--serving-fleet``): a 3-replica
    serving fleet under a multi-tenant loadgen trace; one replica is
    killed mid-trace by the ``replica-crash`` plan (``os._exit`` at
    its nth decode step). Asserts (a) the router stops routing to the
    corpse within one lease window, (b) every loss is a structured
    ``SequenceAborted`` bounded by the shed budget — zero hung
    clients, (c) the supervisor respawns a replica whose startup
    prefetch rides the shared compile store (manifest hit +
    persistent-cache hits + AOT hits; p50 TTFT <= 1.2x warm), and
    (d) the post-drill fleet view shows the membership epoch flipped
    with the new replica live+ready."""
    import statistics
    import tempfile
    import threading as _threading
    import urllib.request

    from deeplearning4j_tpu import environment
    from deeplearning4j_tpu.resilience.elastic import \
        MembershipCoordinator
    from deeplearning4j_tpu.serving.fleet import (FleetSupervisor,
                                                  HttpTransport,
                                                  ServingRouter)
    from deeplearning4j_tpu.serving.gateway import SequenceAborted

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    victim = "r1"
    shed_budget = int(
        environment.get_flag("DL4J_TPU_FLEET_SHED_BUDGET"))
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chaos_fleet_") as d:
        fleet_dir = os.path.join(d, "fleet")
        os.makedirs(fleet_dir)
        script = os.path.join(d, "replica_worker.py")
        with open(script, "w") as f:
            f.write(REPLICA_WORKER % {"repo": repo})
        base_env = dict(os.environ,
                        FLEET_DIR=fleet_dir, LEASE_S=str(lease_s),
                        JAX_PLATFORMS="cpu",
                        DL4J_TPU_COMPILE_STORE=os.path.join(d, "store"),
                        DL4J_TPU_FLEET_PUBLISH_SECS="0.05")
        base_env.pop("DL4J_TPU_FAULT_PLAN", None)
        procs: dict = {}
        logs: dict = {}
        spawn_times: dict = {}
        sup_stop = _threading.Event()

        def spawn(host, plan=None):
            env = dict(base_env, REPLICA_ID=host)
            if plan:
                env["DL4J_TPU_FAULT_PLAN"] = plan
            logs[host] = os.path.join(d, f"{host}.log")
            out = open(logs[host], "w")
            spawn_times[host] = time.perf_counter()
            procs[host] = subprocess.Popen(
                [sys.executable, script], env=env, cwd=repo,
                stdout=out, stderr=subprocess.STDOUT)
            return host

        def tails():
            return {h: open(p).read()[-1500:]
                    for h, p in logs.items() if os.path.exists(p)}

        def fail(why, **extra):
            # tear the fleet down before reporting: the drill never
            # leaks subprocesses, even on a failed assertion path
            sup_stop.set()
            with open(os.path.join(fleet_dir, "STOP"), "w"):
                pass
            for p in list(procs.values()):
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
            out = {"mode": "serving-fleet", "ok": False, "why": why,
                   "wall_s": round(time.perf_counter() - t0, 2),
                   "output_tails": tails()}
            out.update(extra)
            return out

        for i in range(replicas):
            spawn(f"r{i}",
                  plan="replica-crash" if f"r{i}" == victim else None)
        router = ServingRouter(fleet_dir, shed_budget=shed_budget,
                               request_timeout_s=30.0)

        deadline = time.perf_counter() + 300
        while len(router.replicas()) < replicas:
            if time.perf_counter() > deadline:
                return fail("fleet never became ready")
            time.sleep(0.1)

        # stable membership baseline: every replica leased and the
        # epoch committed over the full set before any chaos
        co_sup = MembershipCoordinator(fleet_dir, "supervisor",
                                       n_devices=1,
                                       lease_secs=lease_s)
        all_hosts = sorted(f"r{i}" for i in range(replicas))
        deadline = time.perf_counter() + 120
        epoch0 = None
        while time.perf_counter() < deadline:
            rec = co_sup.epoch_record()
            if rec and sorted(rec.get("members", [])) == all_hosts:
                epoch0 = int(rec["epoch"])
                break
            time.sleep(0.1)
        if epoch0 is None:
            return fail("no committed epoch over the full fleet")

        transport = HttpTransport(timeout_s=30.0)

        def probe_ttfts(host, n=5):
            addr = router.replicas()[host]["addr"]
            vals = []
            for i in range(n):
                out = transport.generate(addr, {
                    "prompt": [1 + i, 2, 3, 4, 5, 6], "max_new": 4,
                    "tenant": "probe", "temperature": None})
                vals.append(float(out["ttft_s"]))
            return vals

        # warm TTFT baseline from the two survivors-to-be (probing
        # the victim would advance its fault counter off-trace)
        warm_ttfts = []
        for h in all_hosts:
            if h != victim:
                warm_ttfts.extend(probe_ttfts(h, n=4))
        warm_p50 = statistics.median(warm_ttfts)

        # capacity supervisor: respawn on eviction, same worker
        # script — its warm path must ride the shared compile store
        next_id = [replicas]

        def _spawn_next():
            host = f"r{next_id[0]}"
            next_id[0] += 1
            return spawn(host)

        sup = FleetSupervisor(co_sup, _spawn_next, target=replicas)

        def _sup_loop():
            while not sup_stop.is_set():
                try:
                    sup.poll()
                except OSError:
                    pass
                sup_stop.wait(0.3)

        sup_thread = _threading.Thread(target=_sup_loop, daemon=True)
        sup_thread.start()

        # the multi-tenant loadgen trace, driven through the router
        rng = np.random.RandomState(17)
        reqs = [{"prompt": rng.randint(0, 64, rng.randint(4, 15)
                                       ).astype(int).tolist(),
                 "max_new": int(rng.randint(6, 11)),
                 "tenant": f"t{i % tenants}"}
                for i in range(trace_requests)]
        results: list = []
        res_lock = _threading.Lock()

        def drive(chunk):
            for r in chunk:
                try:
                    out = router.submit(r["prompt"],
                                        max_new=r["max_new"],
                                        tenant=r["tenant"],
                                        deadline_s=30.0)
                    rec = {"ok": True, "replica": out["replica"],
                           "ttft_s": out["ttft_s"]}
                except SequenceAborted as e:
                    rec = {"ok": False, "aborted": True,
                           "message": str(e)}
                except Exception as e:   # anything else fails the drill
                    rec = {"ok": False, "aborted": False,
                           "error": repr(e)}
                with res_lock:
                    results.append(rec)

        drivers = [_threading.Thread(
            target=drive, args=(reqs[i::threads],), daemon=True)
            for i in range(threads)]
        for th in drivers:
            th.start()

        # the victim self-destructs mid-trace (replica-crash plan);
        # measure how long the router keeps believing in the corpse
        deadline = time.perf_counter() + 120
        while procs[victim].poll() is None:
            if time.perf_counter() > deadline:
                return fail("victim never crashed")
            time.sleep(0.02)
        t_dead = time.perf_counter()
        detect_s = None
        while time.perf_counter() - t_dead < 4 * lease_s:
            if victim not in router.replicas():
                detect_s = time.perf_counter() - t_dead
                break
            time.sleep(0.05)

        for th in drivers:
            th.join(timeout=180)
        hung = sum(1 for th in drivers if th.is_alive())

        # the respawned replica: ready via the compile store
        new_host = f"r{replicas}"
        deadline = time.perf_counter() + 300
        while new_host not in router.replicas():
            if time.perf_counter() > deadline:
                return fail("supervisor never respawned capacity",
                            detect_s=detect_s)
            time.sleep(0.1)
        respawn_ready_s = (time.perf_counter()
                          - spawn_times.get(new_host, t_dead))
        cold_p50 = statistics.median(probe_ttfts(new_host, n=5))
        with open(os.path.join(fleet_dir, "reports",
                               f"{new_host}.json")) as f:
            new_report = json.load(f)
        with urllib.request.urlopen(
                "http://{}/stats".format(
                    router.replicas()[new_host]["addr"]),
                timeout=10) as r:
            new_stats = json.loads(r.read())

        # post-drill fleet view: epoch flipped, new replica live+ready
        survivors = sorted(set(all_hosts) - {victim} | {new_host})
        deadline = time.perf_counter() + 120
        epoch_after = None
        while time.perf_counter() < deadline:
            rec = co_sup.epoch_record()
            if rec and sorted(rec.get("members", [])) == survivors \
                    and int(rec["epoch"]) > epoch0:
                epoch_after = int(rec["epoch"])
                break
            time.sleep(0.1)
        from deeplearning4j_tpu.obs import fleet as obs_fleet
        table = obs_fleet.aggregate(fleet_dir).serving_table()
        new_row = table.get(new_host) or {}

        with open(os.path.join(fleet_dir, "STOP"), "w"):
            pass
        sup_stop.set()
        sup_thread.join(timeout=10)
        clean_exit = True
        for h, p in procs.items():
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                clean_exit = False
        for h in survivors:
            clean_exit = clean_exit and procs[h].returncode == 0

        victim_log = open(logs[victim]).read()
        completed = sum(1 for r in results if r["ok"])
        aborted = sum(1 for r in results if r.get("aborted"))
        errors = [r for r in results
                  if not r["ok"] and not r.get("aborted")]
        ok = (procs[victim].returncode == 17
              and "fault injection: firing" in victim_log
              and detect_s is not None and detect_s <= lease_s + 1.0
              and hung == 0 and not errors
              and completed + aborted == trace_requests
              and aborted <= shed_budget
              and router.sheds <= shed_budget
              and router.reroutes >= 1
              and new_report.get("manifest_hit") is True
              and int(new_report.get("cache", {})
                      .get("persistent_hits", 0)) > 0
              and int(new_stats.get("aot_hits", 0)) > 0
              and cold_p50 <= 1.2 * warm_p50 + 0.01
              and epoch_after is not None
              and bool(new_row.get("ready"))
              and bool(new_row.get("live"))
              and clean_exit)
        res = {"mode": "serving-fleet", "replicas": replicas,
               "victim": victim,
               "victim_rc": procs[victim].returncode,
               "lease_s": lease_s, "detect_s": detect_s,
               "requests": trace_requests, "completed": completed,
               "aborted": aborted, "hung": hung,
               "router_sheds": router.sheds,
               "router_reroutes": router.reroutes,
               "shed_budget": shed_budget,
               "warm_ttft_p50_s": round(warm_p50, 4),
               "cold_ttft_p50_s": round(cold_p50, 4),
               "respawn_ready_s": round(respawn_ready_s, 2),
               "new_replica": new_host,
               "new_manifest_hit": new_report.get("manifest_hit"),
               "new_persistent_hits": int(
                   new_report.get("cache", {})
                   .get("persistent_hits", 0)),
               "new_aot_hits": int(new_stats.get("aot_hits", 0)),
               "new_warm_s": new_report.get("warm_s"),
               "epoch_before": epoch0, "epoch_after": epoch_after,
               "new_replica_ready": bool(new_row.get("ready")),
               "new_replica_live": bool(new_row.get("live")),
               "clean_exit": clean_exit,
               "wall_s": round(time.perf_counter() - t0, 2),
               "ok": bool(ok)}
        if not ok:                  # post-mortem material
            res["output_tails"] = tails()
            res["errors"] = errors[:5]
        return res


def _example_scenario(example: str, plan: str, restarts: int) -> dict:
    """Slice-restart supervision: run the example under the plan env;
    a crash (injected fault escaping to the top) is answered by simply
    re-running the process — completion within the restart budget is
    the assertion. The plan is injected into the FIRST attempt only
    (a seeded plan would fire identically in every restarted process;
    the model is "the fault happened, the restarted job runs clean" —
    exactly what a transient slice failure looks like)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "examples", f"{example}.py")
    if not os.path.exists(script):
        raise SystemExit(f"no such example: {script}")
    attempts = 0
    rc = None
    fault_fired = False
    t0 = time.perf_counter()
    while attempts <= restarts:
        attempts += 1
        env = dict(os.environ,
                   DL4J_TPU_EXAMPLE_FAST="1",
                   JAX_PLATFORMS="cpu")
        env.pop("DL4J_TPU_FAULT_PLAN", None)
        if attempts == 1:
            env["DL4J_TPU_FAULT_PLAN"] = plan
        r = subprocess.run([sys.executable, script], env=env, cwd=repo,
                           timeout=900, capture_output=True, text=True)
        rc = r.returncode
        sys.stdout.write(r.stdout)
        if attempts == 1 and \
                "fault injection: firing" in (r.stderr + r.stdout):
            fault_fired = True       # the harness logs every fire
        if rc == 0:
            break
    # a drill that never fired its fault proved nothing — pick a plan
    # whose site/nth the example actually reaches (the builtin
    # scenarios assert fires the same way)
    return {"mode": "example", "plan": plan, "example": example,
            "attempts": attempts, "returncode": rc,
            "fault_fired": fault_fired,
            "wall_s": round(time.perf_counter() - t0, 2),
            "ok": rc == 0 and fault_fired}


def main() -> int:
    from deeplearning4j_tpu.resilience.faults import (FaultPlan,
                                                      NAMED_PLANS)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--plan", action="append", default=[],
                    help="named plan or raw rule spec (repeatable)")
    ap.add_argument("--example", default=None,
                    help="run examples/<NAME>.py under the plan instead "
                         "of the builtin scenario")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--tol", type=float, default=0.05,
                    help="max |chaos_loss - baseline_loss|")
    ap.add_argument("--restarts", type=int, default=3,
                    help="restart budget for --example supervision")
    ap.add_argument("--elastic", action="store_true",
                    help="multi-host drill: SIGKILL one host of a "
                         "live fleet mid-epoch, assert re-formation + "
                         "resharded restore + baseline-matching "
                         "trajectory (with --plan host-preempt: the "
                         "victim departs via SIGTERM instead)")
    ap.add_argument("--hosts", type=int, default=3,
                    help="fleet size for --elastic")
    ap.add_argument("--serving-fleet", action="store_true",
                    help="elastic serving-fleet drill (ISSUE 18 "
                         "acceptance): 3 leased replicas under a "
                         "multi-tenant trace, one killed mid-trace "
                         "by the replica-crash plan; asserts routing "
                         "detection within one lease window, bounded "
                         "structured sheds, zero hung clients, and a "
                         "compile-store-warm respawn with flipped "
                         "membership epoch")
    ap.add_argument("--list", action="store_true",
                    help="list named plans and exit")
    args = ap.parse_args()
    if args.list:
        for name, spec in NAMED_PLANS.items():
            print(f"{name:<16} {spec}")
        return 0
    if args.serving_fleet:
        results = [_serving_fleet_scenario(replicas=args.hosts)]
        print(json.dumps({"results": results,
                          "ok": all(r["ok"] for r in results)},
                         indent=1))
        return 0 if all(r["ok"] for r in results) else 1
    if args.elastic:
        if args.plan:
            results = [_elastic_preempt_scenario(hosts=args.hosts,
                                                 plan=args.plan[0])]
        else:
            results = [_elastic_scenario(hosts=args.hosts,
                                         kill_host=args.hosts - 1)]
        print(json.dumps({"results": results,
                          "ok": all(r["ok"] for r in results)},
                         indent=1))
        return 0 if all(r["ok"] for r in results) else 1
    if not args.plan:
        ap.error("--plan required (see --list)")

    results = []
    for plan in args.plan:
        parsed = FaultPlan.parse(plan)     # fail fast on bad specs
        if args.example:
            spec = NAMED_PLANS.get(plan, plan)
            results.append(
                _example_scenario(args.example, spec, args.restarts))
        elif any(r.site.startswith("serving") for r in parsed.rules):
            # serving plans drill all three front-end postures: the
            # batched ParallelInference queue, the continuous-batching
            # gateway, and the gateway with CoW prefix sharing +
            # speculative decode live (each parses the plan fresh ->
            # independent rule state, the nth/max counters start over)
            results.append(_serving_scenario(plan))
            results.append(_gateway_scenario(plan))
            results.append(_gateway_cow_scenario(plan))
        elif any(r.site.startswith(("host_death", "coordinator"))
                 for r in parsed.rules):
            results.append(_elastic_preempt_scenario(
                hosts=args.hosts, plan=plan))
        else:
            results.append(_train_scenario(plan, args.epochs, args.tol))
    print(json.dumps({"results": results,
                      "ok": all(r["ok"] for r in results)}, indent=1))
    return 0 if all(r["ok"] for r in results) else 1


if __name__ == "__main__":
    raise SystemExit(main())

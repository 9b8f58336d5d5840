"""Compile-subsystem tests (perf/: persistent XLA cache, AOT warmup,
retrace sentry).

Contracts under test: after ``warmup()`` the first real train step and
first serving request on every declared bucket execute with ZERO new
traces (the sentry's counter is the assertion anchor); the sentry
triggers at budget+1 distinct unplanned shapes (raises under strict,
warns otherwise); the persistent cache dir is populated by one process
and honored by a fresh one; and a tiny fit runs clean under
``sentry.strict()`` — the tier-1 fence that makes any future
retrace-storm regression fail loudly.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.perf import (RetraceBudgetExceeded, WarmupSpec,
                                     compile_cache, sentry, warmup_plan)

REPO = Path(__file__).resolve().parents[1]

X4 = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
Y4 = np.array([[1, 0], [0, 1], [0, 1], [1, 0]], np.float32)


def _mlp(n_in=2, n_out=2, seed=42):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Adam(learning_rate=0.05))
            .weight_init_fn("xavier").list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


# -- AOT warmup -------------------------------------------------------------

def test_warmup_then_fit_and_serve_zero_new_traces():
    net = _mlp()
    sentry.reset()
    report = net.warmup([WarmupSpec(features=(4, 2), labels=(4, 2))])
    assert report["compiled"] == 2          # train step + output fn
    assert report["seconds"] > 0
    before = sentry.total_traces()
    net.fit(X4, Y4)
    net.output(X4)
    assert sentry.total_traces() == before, \
        "fit/serve on a warmed bucket must not trace"
    # trace-free is necessary but not sufficient (jax's AOT path does
    # not feed jit's dispatch cache): the calls must have been SERVED
    # by the stored warmed executables, i.e. XLA compiled nothing
    snap = sentry.stats()
    assert snap["MultiLayerNetwork.train_step"]["aot_hits"] == 1
    assert snap["MultiLayerNetwork.output"]["aot_hits"] == 1
    assert snap["MultiLayerNetwork.train_step"]["compiles"] == 0


def test_warmup_idempotent_and_declares_planned():
    net = _mlp()
    # stats() merges by name across every net this pytest session made;
    # zero the ledger so the assertion sees only THIS net's warmup
    sentry.reset()
    spec = WarmupSpec(features=(4, 2), labels=(4, 2))
    net.warmup([spec])
    again = net.warmup([spec])
    assert again["compiled"] == 0           # already compiled
    snap = sentry.stats()["MultiLayerNetwork.train_step"]
    assert snap["planned_shapes"] >= 1
    assert snap["unplanned_shapes"] == 0


def test_warmup_every_declared_bucket_before_first_batch():
    """Multiple batch buckets warmed up front: a subsequent pass over
    EVERY bucket (the bucketed-iterator traffic pattern) is trace-free.
    """
    net = _mlp()
    specs = warmup_plan([2, 4, 8], feature_dims=(2,), label_dims=(2,))
    assert [s.features for s in specs] == [(2, 2), (4, 2), (8, 2)]
    net.warmup(specs)
    before = sentry.total_traces()
    for b in (2, 4, 8):
        net.fit(X4[:b] if b <= 4 else np.tile(X4, (2, 1)),
                Y4[:b] if b <= 4 else np.tile(Y4, (2, 1)))
        net.output(np.zeros((b, 2), np.float32))
    assert sentry.total_traces() == before


def test_graph_warmup_zero_new_traces():
    conf = (NeuralNetConfiguration.builder().seed(7)
            .updater(upd.Sgd(learning_rate=0.1))
            .graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_out=6, activation="tanh"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(**{"in": InputType.feed_forward(5)})
            .build())
    net = ComputationGraph(conf).init()
    net.warmup([WarmupSpec(features=(4, 5), labels=(4, 3))])
    before = sentry.total_traces()
    x = np.random.default_rng(0).random((4, 5), np.float32)
    y = np.eye(3, dtype=np.float32)[[0, 1, 2, 0]]
    net.fit(x, y)
    net.output(x)
    assert sentry.total_traces() == before


def test_parallel_inference_warmup_covers_all_buckets():
    try:
        from deeplearning4j_tpu.parallel.inference import \
            ParallelInference
    except ImportError as e:                # old-jax container
        pytest.skip(f"parallel package unavailable: {e}")
    net = _mlp(n_in=3)
    pi = ParallelInference(net, buckets=(2, 4))
    try:
        report = pi.warmup(feature_shape=(3,))
        assert report["compiled"] == 2      # one forward per bucket
        before = sentry.total_traces()
        out = pi.output(np.ones((3, 3), np.float32))   # pads to 4
        assert np.asarray(out).shape == (3, 2)
        assert sentry.total_traces() == before, \
            "first serving request on a warmed bucket must not trace"
    finally:
        pi.shutdown()


def test_gpt_decode_warmup_zero_new_traces():
    from deeplearning4j_tpu.zoo import GPTNano
    model = GPTNano(vocab_size=64, max_len=64)
    net = model.init(seq_len=32)
    report = model.warmup_decode(net, n_new=4, batch_sizes=(2,),
                                 prompt_lens=(10,))
    assert report["compiled"] == 1          # one (batch, bucket) pair
    before = sentry.total_traces()
    out = model.generate(net, np.ones((2, 10), np.int32), n_new=4)
    assert out.shape == (2, 14)
    assert sentry.total_traces() == before
    decode = sentry.stats()["CausalTransformerLM.decode"]
    assert decode["aot_hits"] >= 1          # served by the warmed exe


def test_warmup_requires_initialized_network():
    from deeplearning4j_tpu.perf.warmup import warmup_network

    class Empty:
        params = None
    with pytest.raises(RuntimeError, match="init"):
        warmup_network(Empty(), [])


# -- retrace sentry ---------------------------------------------------------

def test_sentry_triggers_at_budget_plus_one():
    import jax.numpy as jnp
    fn = sentry.jit(lambda x: x + 1, name="_test_budget", budget=2)
    with sentry.strict():
        fn(jnp.zeros(1))
        fn(jnp.zeros(2))                    # 2 distinct: at budget, ok
        with pytest.raises(RetraceBudgetExceeded):
            fn(jnp.zeros(3))                # budget+1 → storm


def test_sentry_warns_without_strict(caplog):
    import jax.numpy as jnp
    fn = sentry.jit(lambda x: x * 2, name="_test_warn", budget=1)
    fn(jnp.zeros(1))
    with caplog.at_level("WARNING", logger="deeplearning4j_tpu.perf"):
        fn(jnp.zeros(2))
    assert any("retrace storm" in r.message for r in caplog.records)


def test_warmed_shapes_never_count_against_budget():
    import jax
    import jax.numpy as jnp
    fn = sentry.jit(lambda x: x - 1, name="_test_planned", budget=1)
    with sentry.strict():
        # 4 planned buckets on a budget of 1: warmup declares them,
        # so neither the warmup itself nor the live calls trip
        for n in (1, 2, 3, 4):
            fn.warmup(jax.ShapeDtypeStruct((n,), jnp.float32))
        for n in (1, 2, 3, 4):
            fn(jnp.zeros(n))


def test_registry_releases_dead_networks():
    """The sentry ledger must not leak: a collected network's
    FunctionStats leave the registry (weakrefs), so long-running
    processes that construct models repeatedly stay bounded."""
    import gc
    from deeplearning4j_tpu.perf.sentry import _LOCK, _live_stats

    def make():
        net = _mlp(seed=11)
        net.fit(X4, Y4)
        net.output(X4)

    gc.collect()                 # clear earlier tests' cyclic garbage
    with _LOCK:
        n0 = len(_live_stats())
    make()
    gc.collect()
    with _LOCK:
        n1 = len(_live_stats())
    assert n1 == n0, "dead network's sentry ledgers were not released"


def test_strict_budget_override():
    import jax.numpy as jnp
    fn = sentry.jit(lambda x: x, name="_test_override")   # global budget
    with sentry.strict(budget=1):
        fn(jnp.zeros(5))
        with pytest.raises(RetraceBudgetExceeded):
            fn(jnp.zeros(6))


def test_tiny_fit_under_strict_sentry():
    """CI fence (tier-1, not slow): a tiny uniform-shape fit + serve
    must run clean under ``sentry.strict()``. A future PR that lets an
    unbucketed shape slip into a hot path fails HERE, loudly, instead
    of degrading TPU throughput silently."""
    net = _mlp(seed=3)
    it = [(X4, Y4)] * 3
    with sentry.strict(budget=8):
        net.fit(iter(it))
        net.output(X4)


# -- persistent compile cache -----------------------------------------------

def test_cache_stats_shape():
    stats = compile_cache.cache_stats()
    assert {"dir", "enabled", "entries", "bytes", "compile_requests",
            "persistent_hits", "persistent_misses"} <= stats.keys()


_DIR_CHILD = r"""
import json
import jax
from jax._src import xla_bridge
updates = []
_update = jax.config.update
def spy(name, val):
    updates.append(name)
    return _update(name, val)
jax.config.update = spy
from deeplearning4j_tpu.perf import compile_cache
print(json.dumps({
    "dir": compile_cache.cache_dir(),
    "stats_dir": compile_cache.cache_stats()["dir"],
    "jax_dir": jax.config.jax_compilation_cache_dir,
    "dir_updates": updates.count("jax_compilation_cache_dir"),
    "floor_updates": updates.count(
        "jax_persistent_cache_min_compile_time_secs"),
    "backend_initialized": xla_bridge.backends_are_initialized(),
}))
"""

_CACHE_VARS = ("JAX_COMPILATION_CACHE_DIR", "DL4J_TPU_COMPILE_CACHE",
               "DL4J_TPU_COMPILE_STORE", "JAX_PLATFORMS")


def _cache_dir_child(**env_set):
    """What a FRESH process that only imports the package ends up
    with, under exactly the given cache/platform variables."""
    env = {k: v for k, v in os.environ.items() if k not in _CACHE_VARS}
    env.update(env_set)
    r = subprocess.run([sys.executable, "-c", _DIR_CHILD], cwd=REPO,
                       env=env, timeout=120, capture_output=True,
                       text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # configured at import from config alone — no backend touched
    assert out["backend_initialized"] is False
    assert out["dir"] == out["stats_dir"]
    return out


@pytest.mark.parametrize("case", [
    "jax_env_dir_wins", "auto_detected_default", "cpu_named_default_off",
    "cpu_named_explicit_dir"])
def test_cache_dir_resolution(case, tmp_path):
    """Where the persistent cache lives (perf/compile_cache.py): a set
    JAX_COMPILATION_CACHE_DIR is used as is and no other directory is
    ever set from code; otherwise the default is the fixed in-checkout
    path, identical across fresh processes; a process NAMED as CPU-only
    skips only that default (jaxlib 0.9.0's XLA:CPU AOT loader logs a
    machine-mismatch error per cache hit)."""
    jax_dir, flag_dir = str(tmp_path / "jax"), str(tmp_path / "flag")
    if case == "jax_env_dir_wins":
        out = _cache_dir_child(
            JAX_COMPILATION_CACHE_DIR=jax_dir, JAX_PLATFORMS="cpu",
            DL4J_TPU_COMPILE_CACHE=flag_dir,
            DL4J_TPU_COMPILE_STORE=str(tmp_path / "store"))
        assert out["dir"] == out["jax_dir"] == jax_dir
        assert out["dir_updates"] == 0
        assert out["floor_updates"] == 1    # floors still installed
        assert not os.path.exists(flag_dir)
        assert not os.path.exists(tmp_path / "store")
    elif case == "auto_detected_default":
        first, second = _cache_dir_child(), _cache_dir_child()
        assert first["dir"] == str(REPO / ".jax_cache")
        assert first["dir"] == first["jax_dir"]
        assert second == first              # a fixed path: no pid/time
    elif case == "cpu_named_default_off":
        out = _cache_dir_child(JAX_PLATFORMS="cpu")
        assert out["dir"] is None and out["dir_updates"] == 0
    else:
        out = _cache_dir_child(JAX_PLATFORMS="cpu",
                               DL4J_TPU_COMPILE_CACHE=flag_dir)
        assert out["dir"] == out["jax_dir"] == flag_dir
        assert out["dir_updates"] == 1


def test_default_cache_off_in_this_cpu_named_process(monkeypatch):
    """In-process view of the same gate (conftest names the CPU)."""
    monkeypatch.delenv("DL4J_TPU_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.configure() is None
    monkeypatch.setenv("DL4J_TPU_COMPILE_CACHE", "off")
    assert compile_cache.configure() is None
    compile_cache.configure_from_env()


def test_configure_disable_values(tmp_path):
    for off in ("", "0", "off", "none"):
        assert compile_cache.configure(cache_dir=off) is None
    active = compile_cache.configure(cache_dir=str(tmp_path / "cc"))
    assert active == str(tmp_path / "cc") and os.path.isdir(active)
    # restore the ambient env-configured state for later tests
    compile_cache.configure_from_env()


_CACHE_CHILD = r"""
import json, sys
import numpy as np
from deeplearning4j_tpu.perf import compile_cache
from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn import updaters as upd

conf = (NeuralNetConfiguration.builder().seed(42)
        .updater(upd.Adam(learning_rate=0.05))
        .weight_init_fn("xavier").list()
        .layer(DenseLayer(n_out=8, activation="tanh"))
        .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.feed_forward(2)).build())
net = MultiLayerNetwork(conf).init()
x = np.array([[0, 0], [0, 1], [1, 0], [1, 1]], np.float32)
y = np.array([[1, 0], [0, 1], [0, 1], [1, 0]], np.float32)
net.fit(x, y)
print(json.dumps(compile_cache.cache_stats()))
"""


@pytest.mark.slow
def test_cache_populated_and_honored_across_processes(tmp_path):
    """Process 1 fills DL4J_TPU_COMPILE_CACHE; a FRESH process 2 running
    the identical workload compiles nothing XLA-side (every eligible
    compile request is a persistent hit)."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               DL4J_TPU_COMPILE_CACHE=str(tmp_path / "cache"))
    env.pop("XLA_FLAGS", None)

    def run():
        r = subprocess.run([sys.executable, "-c", _CACHE_CHILD],
                           cwd=REPO, env=env, timeout=420,
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    first = run()
    assert first["enabled"] and first["dir"] == str(tmp_path / "cache")
    assert first["entries"] > 0, first
    assert first["persistent_hits"] == 0
    second = run()
    assert second["persistent_hits"] > 0, second
    assert second["persistent_hits"] == second["compile_requests"], \
        second                               # every compile pre-paid

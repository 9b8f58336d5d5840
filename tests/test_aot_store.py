"""Executables by a key that needs no trace (``perf/aot_store.py``,
``sentry.jit(identity=...)``).

Contracts under test: a program put by one owner is LOADED by the
next, neither traced nor lowered, and gives the traced run's outputs
bit for bit, donation included; **key equal ⇒ program equal**, one
constructor argument or configuration field changed at a time (the
key's completeness is the whole risk of a stale executable, so the
fence is this test); a flag, a byte of the package's source and a
sharding each change the key; a damaged artifact is quarantined and
the call compiles; an owner that holds code from outside the package
states no identity; a load counts and records as a persistent-cache
hit does; and a program that was put leaves no entry in the XLA plane.
"""
import contextlib
import dataclasses
import inspect
import json
import pickle
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                   NeuralNetConfiguration)
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.nn.config import (InputType,
                                          MultiLayerConfiguration)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.obs import trace
from deeplearning4j_tpu.ops import latent as L
from deeplearning4j_tpu.ops import moe as M
from deeplearning4j_tpu.ops import ssm
from deeplearning4j_tpu.ops.rotary import RopeRule
from deeplearning4j_tpu.perf import aot_store, compile_cache, sentry
from deeplearning4j_tpu.serving import DecodeScheduler
from deeplearning4j_tpu.zoo.gpt import CausalTransformerLM

REPO = Path(__file__).resolve().parents[1]
K = 2       # steps a loop


@pytest.fixture
def store(tmp_path):
    """The persistent cache, and with it the executable store, in a
    directory of this test's own; both off again afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    compile_cache.configure(cache_dir=str(tmp_path / "cache"))
    sentry.reset()
    try:
        yield aot_store.store()
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()
        compile_cache.configure_from_env()


# -- toy owners ---------------------------------------------------------------

def _lm(**kw):
    base = dict(vocab_size=64, hidden=32, n_layers=1, n_heads=2,
                n_kv_heads=1, max_len=64, seed=9)
    return CausalTransformerLM(**{**base, **kw})


def _sched(model=None, **kw):
    model = model or _lm()
    base = dict(max_slots=2, block=16, max_context=64)
    return DecodeScheduler(model, model.init(), **{**base, **kw})


class _Req:
    def __init__(self, prompt, max_new):
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new, self.temperature, self.eos_id = max_new, None, None
        self.tokens = []

    def push(self, tok):
        self.tokens.append(int(tok))

    def finish(self):
        pass

    def fail(self, e):
        raise e


def _serve(sched):
    """Two prompts of two buckets through admission and decoding."""
    rng = np.random.default_rng(3)
    reqs = [_Req(rng.integers(0, 64, n), 6) for n in (5, 20)]
    for r in reqs:
        assert sched.admit(r)
    while sched.active_count() or sched._inflight is not None:
        sched.step()
    return [r.tokens for r in reqs]


def _graph(layer=None, seed=11):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .updater(upd.Nesterovs(learning_rate=0.05))
            .graph_builder().add_inputs("in")
            .add_layer("d", layer or DenseLayer(n_out=8,
                                                activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                          loss="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(**{"in": InputType.feed_forward(4)})
            .build())
    return ComputationGraph(conf).init()


def _mln_conf(**kw):
    base = dict(
        layers=[DenseLayer(n_out=8, activation="relu"),
                OutputLayer(n_out=2, activation="softmax",
                            loss="mcxent")],
        seed=7, updater=upd.Nesterovs(learning_rate=0.05),
        input_type=InputType.feed_forward(4))
    return MultiLayerConfiguration(**{**base, **kw})


def _batches(n=2 * K, b=8):
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n):
        x = rng.standard_normal((b, 4)).astype(np.float32)
        out.append(DataSet(x, np.eye(2, dtype=np.float32)[
            (x.sum(1) > 0).astype(int)]))
    return out


def _fit(net):
    """``fit(steps_per_loop=K)``; the parameters it leaves, and whether
    the ones it started from were donated."""
    before = jax.tree.leaves(net.params)
    net.fit(ListDataSetIterator(_batches()), steps_per_loop=K)
    return ([np.asarray(a) for a in jax.tree.leaves(net.params)],
            all(a.is_deleted() for a in before))


def _loaded(name):
    snap = sentry.stats().get(name, {})
    return snap.get("store_hits", 0), snap.get("traces", 0)


# -- (a) the round trip -------------------------------------------------------

def test_scheduler_programs_load_without_a_trace(store):
    first = _sched()
    report = first.warmup(prompt_lens=[5, 20])
    assert report["compiled"] == 3          # the step, two buckets
    assert store.counters()["puts"] == 3
    traced = _serve(first)
    sentry.reset()
    again = _sched()
    assert again.warmup(prompt_lens=[5, 20])["compiled"] == 3
    served = _serve(again)
    assert sentry.total_traces() == 0
    assert _loaded("serving.decode_step") == (1, 0)
    assert _loaded("serving.prefill") == (2, 0)
    assert store.counters()["hits"] == 3
    assert served == traced
    # warming again is idempotent: nothing is looked up twice
    assert again.warmup(prompt_lens=[5, 20])["compiled"] == 0
    assert store.counters()["hits"] == 3


@pytest.mark.parametrize("make", [_graph, lambda: MultiLayerNetwork(
    _mln_conf()).init()], ids=["graph", "mln"])
def test_fit_loop_loads_without_a_trace(store, make):
    traced, donated = _fit(make())
    assert donated
    name = next(n for n in sentry.stats() if n.endswith("train_loop"))
    assert sentry.stats()[name]["store_misses"] == 1
    sentry.reset()
    loaded, donated = _fit(make())
    assert donated, "a loaded executable donates as the compiled one"
    assert _loaded(name) == (1, 0)
    assert sentry.stats()[name]["aot_hits"] == 2    # both groups
    assert all(np.array_equal(a, b) for a, b in zip(traced, loaded))


_CHILD = """
import json, sys
import numpy as np
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
import test_aot_store as t
from deeplearning4j_tpu.perf import sentry
sched = t._sched()
sched.warmup(prompt_lens=[5, 20])
tokens = t._serve(sched)
params, _ = t._fit(t._graph(seed=int(sys.argv[1])))
print(json.dumps({{"stats": sentry.stats(), "tokens": tokens,
                   "sum": float(sum(np.abs(p).sum() for p in params))}}))
"""


def test_a_second_process_loads_what_the_first_put(tmp_path):
    """The key holds nothing of one process (an address, a counter):
    a fresh interpreter finds every program, under another init seed
    too (a seed reaches the programs only as arguments)."""
    env = {**__import__("os").environ, "JAX_PLATFORMS": "cpu",
           "DL4J_TPU_COMPILE_CACHE": str(tmp_path / "cache")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for seed in (11, 12):
        proc = subprocess.run(
            [sys.executable, "-c", _CHILD.format(
                repo=str(REPO), tests=str(REPO / "tests")), str(seed)],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    cold, warm = (r["stats"] for r in runs)
    names = ("serving.decode_step", "serving.prefill",
             "ComputationGraph.train_loop")
    assert [cold[n]["store_misses"] for n in names] == [1, 2, 1]
    assert [warm[n]["store_hits"] for n in names] == [1, 2, 1]
    assert all(warm[n]["traces"] == 0 for n in names)
    assert runs[0]["tokens"] == runs[1]["tokens"]


# -- (b) key equal => program equal -------------------------------------------

@contextlib.contextmanager
def _captured_calls():
    """Every ``SentryJit.warmup`` and train-loop call, recorded as
    ``(fn, args, kwargs)`` and not run."""
    calls = []

    class Stop(Exception):
        pass

    real_warmup, real_call = (sentry.SentryJit.warmup,
                              sentry.SentryJit.__call__)

    def warmup(self, *args, **kwargs):
        calls.append((self, args, kwargs))
        return 0.0

    def call(self, *args, **kwargs):
        if self.name.endswith("train_loop"):
            calls.append((self, args, kwargs))
            raise Stop
        return real_call(self, *args, **kwargs)

    sentry.SentryJit.warmup, sentry.SentryJit.__call__ = warmup, call
    try:
        yield calls, Stop
    finally:
        sentry.SentryJit.warmup, sentry.SentryJit.__call__ = (
            real_warmup, real_call)


class _Programs:
    """An owner's programs as ``[key, lower]`` rows, lowered lazily."""

    def __init__(self, calls):
        self.rows = []
        for fn, args, kwargs in calls:
            said = fn._identity()
            assert said is not None, fn.name
            key = aot_store.program_key(fn.name, fn._jit_kwargs, args,
                                        kwargs, said)
            self.rows.append([key, (fn, args, kwargs), None])

    def keys(self):
        return [r[0] for r in self.rows]

    def text(self, i):
        row = self.rows[i]
        if row[2] is None:
            fn, args, kwargs = row[1]
            row[2] = fn.lower(*args, **kwargs).as_text()
        return row[2]


def _sched_programs(model_kw=None, **sched_kw):
    with _captured_calls() as (calls, _):
        sched = _sched(_lm(**(model_kw or {})), **sched_kw)
        sched.warmup(prompt_lens=[5])
    return _Programs(calls)


def _loop_programs(net):
    with _captured_calls() as (calls, Stop):
        with pytest.raises(Stop):
            net.fit(ListDataSetIterator(_batches()), steps_per_loop=K)
    return _Programs(calls)


def _assert_key_equal_implies_program_equal(base, other):
    """Every program of ``other`` whose key a program of ``base`` has
    lowers to that program's text. Returns how many keys were equal."""
    equal = 0
    for i, key in enumerate(other.keys()):
        if key in base.keys():
            equal += 1
            assert other.text(i) == base.text(base.keys().index(key)), \
                "one key, two programs: the key is missing something"
    return equal


_LATENT = L.LatentSpec(q_rank=24, kv_rank=16, nope=8, rope=8, v=8)
_HYBRID = ssm.HybridSpec(kinds=("mamba2",), d_inner=64, n_heads=4,
                         d_state=8, chunk=16)
#: every constructor argument of the scheduler and of the model it
#: serves, one changed at a time: ``(scheduler's, model's)``
SCHED_CASES = {
    "max_slots": (dict(max_slots=3), {}),
    "block": (dict(block=8), {}),
    "n_pages": (dict(n_pages=7), {}),
    "max_context": (dict(max_context=32), {}),
    "sample": (dict(sample=True), {}),
    "top_k": (dict(top_k=5), {}),
    "top_p": (dict(top_p=0.9), {}),
    "seed": (dict(seed=1), {}),
    "spec_k": (dict(spec_k=2), {}),
    "prefix_sharing": (dict(prefix_sharing=True), {}),
    "model.vocab_size": ({}, dict(vocab_size=80)),
    "model.hidden": ({}, dict(hidden=64)),
    "model.n_layers": ({}, dict(n_layers=2)),
    "model.n_heads": ({}, dict(n_heads=4)),
    "model.n_kv_heads": ({}, dict(n_kv_heads=2)),
    "model.max_len": ({}, dict(max_len=128)),
    "model.ffn_mult": ({}, dict(ffn_mult=2)),
    "model.rope_theta": ({}, dict(rope_theta=None)),
    "model.dropout": ({}, dict(dropout=0.1)),
    "model.sequence_parallel": ({}, dict(sequence_parallel="ring")),
    "model.remat": ({}, dict(remat=True)),
    "model.tie_embeddings": ({}, dict(tie_embeddings=True)),
    "model.serve_quant": ({}, dict(serve_quant="int8")),
    "model.cache_quant": ({}, dict(cache_quant="int8")),
    "model.seed": ({}, dict(seed=10)),
    "model.updater": ({}, dict(updater=upd.Sgd(learning_rate=0.5))),
    "model.compute_dtype": ({}, dict(compute_dtype="bfloat16")),
    "model.mixer": ({}, dict(mixer="power_retention")),
    "model.latent": ({}, dict(mixer="latent", latent=_LATENT)),
    "model.experts": ({}, dict(
        mixer="latent", latent=_LATENT, experts=M.ExpertSpec(
            width=16, n_held=2, n_routed=8, top_k=2))),
    "model.hybrid": ({}, dict(mixer="hybrid", hybrid=_HYBRID,
                              rope_theta=None)),
    "model.embedding_multiplier": ({}, dict(embedding_multiplier=2.0)),
    "model.residual_multiplier": ({}, dict(residual_multiplier=0.5)),
    "model.logits_scaling": ({}, dict(logits_scaling=4.0)),
    "model.attention_multiplier": ({}, dict(attention_multiplier=0.1)),
    "model.norm_eps": ({}, dict(norm_eps=1e-3)),
    "model.window": ({}, dict(window=8)),
    "model.window_layers": ({}, dict(window=8, window_layers=[0])),
    "model.rope_layers": ({}, dict(rope_layers=[0])),
    "model.head_dim": ({}, dict(head_dim=8)),
    "model.heads_by_layer": ({}, dict(window=8, head_dim=16,
                                      heads_by_layer=[4])),
    "model.rope_by_kind": ({}, dict(window=8, rope_by_kind={
        "full": RopeRule(theta=1e4),
        "window": RopeRule(theta=1e4, rotary_dim=8,
                           yarn=(4.0, 16.0, 4.0, 1.0), factor=1.1)})),
    "model.attn_gate": ({}, dict(attn_gate=True)),
}
#: what a served program reads only as arguments, or not at all: the
#: key must NOT move with these, or every new seed is a cold start
SAME_KEY = {"model.seed", "model.updater"}


def test_every_constructor_argument_has_a_case():
    sched = set(inspect.signature(DecodeScheduler.__init__).parameters)
    model = set(inspect.signature(
        CausalTransformerLM.__init__).parameters)
    wanted = (sched - {"self", "model", "net"}) | {
        f"model.{p}" for p in model - {"self"}}
    assert wanted == set(SCHED_CASES), (
        "a constructor argument without a case in SCHED_CASES: add "
        "one, so that the key is held to it")


@pytest.fixture(scope="module")
def sched_base():
    return _sched_programs()


@pytest.mark.parametrize("case", sorted(SCHED_CASES))
def test_scheduler_key_equal_implies_program_equal(case, sched_base):
    sched_kw, model_kw = SCHED_CASES[case]
    other = _sched_programs(model_kw, **sched_kw)
    equal = _assert_key_equal_implies_program_equal(sched_base, other)
    if case in SAME_KEY:
        assert equal == len(other.keys()) == len(sched_base.keys())


#: every field of the toy configuration and of its first layer, one
#: changed at a time
CONF_CASES = {
    "layers": dict(layers=[
        DenseLayer(n_out=8, activation="relu"),
        DenseLayer(n_out=8, activation="relu"),
        OutputLayer(n_out=2, activation="softmax", loss="mcxent")]),
    "seed": dict(seed=8),
    "dtype": dict(dtype="bfloat16"),
    "compute_dtype": dict(compute_dtype="bfloat16"),
    "updater": dict(updater=upd.Nesterovs(learning_rate=0.01)),
    "gradient_normalization": dict(
        gradient_normalization="ClipL2PerLayer"),
    "gradient_normalization_threshold": dict(
        gradient_normalization_threshold=2.0),
    "input_type": dict(input_type=InputType("ff", (4, 1))),
    "backprop_type": dict(backprop_type="TruncatedBPTT"),
    "tbptt_fwd_length": dict(tbptt_fwd_length=10),
    "tbptt_back_length": dict(tbptt_back_length=10),
    "mini_batch": dict(mini_batch=False),
    "input_preprocessors": None,        # set below: needs the module
    "tied_weights": None,
}
LAYER_CASES = {
    "name": "first", "activation": "tanh", "weight_init": "relu",
    "bias_init": 0.5, "l1": 1e-3, "l2": 1e-3, "weight_decay": 1e-2,
    "dropout": 0.5, "updater": upd.Sgd(learning_rate=0.3),
    "learning_rate": 0.3, "trainable": False, "constraints": None,
    "weight_noise": None, "n_in": 4, "n_out": 6,
    "has_layer_norm": True, "has_bias": False,
}


def _conf_case(case):
    from deeplearning4j_tpu.nn import constraints, preprocessors
    if case == "input_preprocessors":
        return _mln_conf(input_preprocessors={
            1: preprocessors.ComposableInputPreProcessor([])})
    if case == "tied_weights":
        return _mln_conf(
            layers=[DenseLayer(n_out=4, activation="relu"),
                    DenseLayer(n_out=4, activation="relu"),
                    OutputLayer(n_out=2, activation="softmax",
                                loss="mcxent")],
            tied_weights=[[1, "W", 0, "W", True]])
    if case.startswith("layer."):
        field = case[len("layer."):]
        value = LAYER_CASES[field]
        if field == "constraints":
            value = [constraints.MaxNormConstraint(1.0)]
        if field == "weight_noise":
            value = constraints.DropConnect(0.5)
        first = DenseLayer(**{**dict(n_out=8, activation="relu"),
                              field: value})
        return _mln_conf(layers=[first, OutputLayer(
            n_out=2, activation="softmax", loss="mcxent")])
    return _mln_conf(**CONF_CASES[case])


def test_every_configuration_field_has_a_case():
    assert {f.name for f in dataclasses.fields(
        MultiLayerConfiguration)} == set(CONF_CASES)
    assert {f.name for f in dataclasses.fields(DenseLayer)} \
        == set(LAYER_CASES)


@pytest.fixture(scope="module")
def loop_base():
    return _loop_programs(MultiLayerNetwork(_mln_conf()).init())


@pytest.mark.parametrize("case", sorted(CONF_CASES) + sorted(
    f"layer.{f}" for f in LAYER_CASES))
def test_train_loop_key_equal_implies_program_equal(case, loop_base):
    other = _loop_programs(MultiLayerNetwork(_conf_case(case)).init())
    equal = _assert_key_equal_implies_program_equal(loop_base, other)
    if case == "seed":      # a seed reaches the loop as its rngs
        assert equal == 1


def test_graph_and_numerics_and_context_are_in_the_key():
    """What is not a field of the configuration: the net's class, the
    numerics monitor's settings, the ambient distributed context."""
    from deeplearning4j_tpu.parallel.mesh import (distributed_context,
                                                  make_mesh)
    base = _loop_programs(_graph()).keys()
    assert _loop_programs(_graph()).keys() == base
    assert _loop_programs(_graph(seed=12)).keys() == base
    watched = _graph()
    watched.monitor_numerics(every=1000)
    assert _loop_programs(watched).keys() != base
    with distributed_context(make_mesh({"seq": 2})):
        assert _loop_programs(_graph()).keys() != base


def _toy_key(x, **kw):
    return aot_store.program_key("toy", {"donate_argnums": (0,)},
                                 (x,), {}, {"what": "toy"}, **kw)


def test_a_flag_changes_the_key(monkeypatch):
    x = jnp.ones((4,))
    base = _toy_key(x)
    assert _toy_key(x) == base
    monkeypatch.setenv("DL4J_TPU_FLASH_MIN_T", "2048")
    assert _toy_key(x) != base
    monkeypatch.delenv("DL4J_TPU_FLASH_MIN_T")
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/nowhere")
    assert _toy_key(x) != base
    monkeypatch.undo()
    with jax.default_matmul_precision("highest"):
        assert _toy_key(x) != base
    assert _toy_key(x) == base


def test_a_byte_of_the_source_changes_the_key(tmp_path):
    pkg = REPO / "deeplearning4j_tpu" / "perf"
    copy = tmp_path / "perf"
    shutil.copytree(pkg, copy, ignore=shutil.ignore_patterns(
        "__pycache__"))
    x = jnp.ones((4,))
    base = _toy_key(x, package_root=copy)
    assert aot_store.package_digest(copy) == aot_store.package_digest(pkg)
    victim = copy / "warmup.py"
    data = bytearray(victim.read_bytes())
    data[len(data) // 2] ^= 1
    victim.write_bytes(bytes(data))
    aot_store._digests.pop(str(copy))       # once a process, else
    assert _toy_key(x, package_root=copy) != base


def test_a_sharding_and_a_weak_type_change_the_key():
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jnp.ones((4,))
    base = _toy_key(x)
    devs = jax.devices()
    assert _toy_key(jax.device_put(x, devs[1])) != base
    mesh = jax.make_mesh((2,), ("d",), devices=devs[:2])
    split = _toy_key(jax.device_put(x, NamedSharding(mesh, P("d"))))
    whole = _toy_key(jax.device_put(x, NamedSharding(mesh, P())))
    assert len({base, split, whole}) == 3
    other = jax.make_mesh((2,), ("d",), devices=devs[2:4])
    assert _toy_key(jax.device_put(
        x, NamedSharding(other, P("d")))) != split
    # a Python scalar is weakly typed; an array of its value is not
    assert _toy_key(1.0) != _toy_key(jnp.float32(1.0))
    assert _toy_key(jax.ShapeDtypeStruct((4,), jnp.float32)) == base


# -- (c) damaged artifacts ----------------------------------------------------

def _toy_fn(scale=3.0):
    return sentry.jit(lambda x: {"y": jnp.tanh(x) * scale},
                      name="toy.program",
                      identity=lambda: {"scale": scale})


def _damage(path: Path, how: str, store):
    blob = path.read_bytes()
    if how == "torn":
        path.write_bytes(blob[:-8] + bytes(8))
    elif how == "truncated":
        path.write_bytes(blob[:len(blob) // 2])
    elif how == "wrong_tree":
        # another program's sound artifact under this key
        fp = json.loads(blob.split(b"\n", 2)[1])["fingerprint"]
        entry = pickle.loads(aot_store._decompress(store.get(fp)))
        entry["in_tree"] = jax.tree.structure(((1, 2), {}))
        store.put(fp, aot_store._compress(pickle.dumps(entry)))
    elif how == "not_a_pickle":
        fp = json.loads(blob.split(b"\n", 2)[1])["fingerprint"]
        store.put(fp, b"no executable here")


@pytest.mark.parametrize("how", ["torn", "truncated", "wrong_tree",
                                 "not_a_pickle"])
def test_a_damaged_artifact_is_quarantined_and_the_call_compiles(
        store, how):
    x = jnp.linspace(-1.0, 1.0, 8)
    want = np.asarray(_toy_fn()(x)["y"])
    (entry,) = store.objects_dir.glob("*.cse")
    _damage(entry, how, store)
    sentry.reset()
    fn = _toy_fn()
    assert np.array_equal(np.asarray(fn(x)["y"]), want)
    snap = fn.stats.snapshot()
    assert (snap["traces"], snap["store_hits"],
            snap["store_misses"]) == (1, 0, 1)
    assert store.counters()["quarantined"] == 1
    assert len(list((store.fence_dir / "corrupt").iterdir())) == 1
    # the compile put a sound artifact in its place
    sentry.reset()
    fn = _toy_fn()
    assert np.array_equal(np.asarray(fn(x)["y"]), want)
    assert fn.stats.snapshot()["store_hits"] == 1
    assert sentry.total_traces() == 0


def test_the_artifacts_keep_to_a_byte_limit_by_least_recent_load(store):
    x = jnp.ones((8,))
    for scale in (1.0, 2.0, 3.0):
        _toy_fn(scale)(x)
    sizes = sorted(p.stat().st_size
                   for p in store.objects_dir.glob("*.cse"))
    assert len(sizes) == 3
    _toy_fn(1.0)(x)                 # loaded: now the most recent
    store.max_bytes = sum(sizes) + sizes[0] // 2
    _toy_fn(4.0)(x)                 # a fourth does not fit
    assert store.counters()["evicted"] == 1
    sentry.reset()
    # the one that went is last: finding it gone puts it, and evicts
    for scale, hit in ((1.0, 1), (3.0, 1), (4.0, 1), (2.0, 0)):
        fn = _toy_fn(scale)
        fn(x)
        assert fn.stats.snapshot()["store_hits"] == hit, scale


# -- (d) code from outside the package ----------------------------------------

@dataclasses.dataclass
class TwiceDense(DenseLayer):
    """A user's layer: defined here, outside the digested package."""

    def apply(self, params, state, x, **kw):
        y, state = super().apply(params, state, x, **kw)
        return 2.0 * y, state


def test_a_users_layer_states_no_identity_and_leaves_no_artifact(store):
    net = _graph(TwiceDense(n_out=8, activation="relu"))
    _fit(net)
    assert net._train_loop_fn._identity is None
    snap = sentry.stats()["ComputationGraph.train_loop"]
    assert (snap["traces"], snap["store_hits"],
            snap["store_misses"]) == (1, 0, 0)
    assert store.counters()["puts"] == 0
    assert not list(store.objects_dir.glob("*.cse"))
    with pytest.raises(aot_store.CannotSay):
        aot_store.describe(TwiceDense(n_out=8))
    with pytest.raises(aot_store.CannotSay):
        aot_store.describe({"activation": lambda x: x})
    with pytest.raises(aot_store.CannotSay):
        aot_store.describe([jnp.ones((2,))])


def test_without_a_cache_directory_nothing_changes():
    """This CPU-named process has no persistent cache: an identity is
    never asked for, and the entry point traces as it always did."""
    assert compile_cache.cache_dir() is None
    asked = []
    fn = sentry.jit(lambda x: x + 1.0, name="toy.no_store",
                    identity=lambda: asked.append(1) or {})
    fn(jnp.ones((3,)))
    fn.warmup(jax.ShapeDtypeStruct((5,), jnp.float32))
    snap = fn.stats.snapshot()
    assert (snap["traces"], snap["store_hits"],
            snap["store_misses"]) == (2, 0, 0)
    assert not asked


# -- (e) the records keep their meaning ---------------------------------------

def test_a_load_counts_and_records_as_a_persistent_hit(store):
    x = jnp.ones((8,))
    _toy_fn()(x)                    # compiled and put
    compile_cache.reset_counters()
    sentry.reset()
    t0 = trace.now()
    fn = _toy_fn()
    fn(x)
    assert compile_cache.counters() == {
        "compile_requests": 1, "persistent_hits": 1,
        "persistent_misses": 0}
    mine = {r.name: r for r in trace.records(since=t0)
            if r.name.startswith("compile/") and r.cause == "toy.program"}
    assert set(mine) == {"compile/backend_compile",
                         "compile/cache_retrieval"}
    outer, inner = (mine["compile/backend_compile"].stamps,
                    mine["compile/cache_retrieval"].stamps)
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    snap = sentry.stats()["toy.program"]
    assert snap["store_hits"] == 1 and snap["backend_compile_s"] > 0
    assert snap["jaxpr_trace_s"] == snap["jaxpr_to_mlir_s"] == 0
    from deeplearning4j_tpu.obs import metrics
    text = metrics.exposition()
    assert 'dl4j_tpu_aot_store_hits_total{function="toy.program"} 1' \
        in text
    assert 'dl4j_tpu_aot_store_misses_total{function="toy.program"} 0' \
        in text


# -- (f) no executable on disk twice ------------------------------------------

def test_a_program_that_was_put_leaves_no_xla_plane_entry(store):
    x = jnp.ones((8,))

    def named(x):
        return jnp.tanh(x) * 3.0

    def plain(x):
        return jnp.tanh(x) * 5.0

    sentry.jit(named, name="toy.named", identity=lambda: {"n": 1})(x)
    sentry.jit(plain, name="toy.plain")(x)
    entries = [p.name for p in Path(compile_cache.cache_dir()).iterdir()
               if p.name.endswith("-cache")]
    assert any(n.startswith("jit_plain-") for n in entries)
    assert not any(n.startswith("jit_named-") for n in entries)
    assert len(list(store.objects_dir.glob("*.cse"))) == 1

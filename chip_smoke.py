"""Chip smoke: the trainer and the serving gateway, end to end, on the
attached TPU — through the entry points a user calls.

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --multichip  # four chips: ParallelWrapper only

One process; it needs the chip and fails (non-zero exit, traceback on
stderr) on the first phase that fails — nothing is caught and skipped,
and no TPU is an error. Phases of the default run:

1. device  — ``jax.devices()`` must be a TPU; versions, compile-cache
   directory and ``native.available()`` are printed.
2. train   — GPT-2-small-width ``CausalTransformerLM`` (depth 12, bf16
   compute), ``net.fit`` at 16 x 1024 tokens: finite falling loss, and
   the flash-attention and fused-norm Pallas kernels are in the
   lowered step (not their jnp references).
3. serve   — ``ServingGateway`` over the same net: warmup, 8 prompts
   from two tenants, every stream completes, zero retraces after
   warmup, pager invariants, paged greedy decode == dense ``generate``
   (see :func:`check_paged_equals_dense`); then again with int8 KV
   pages. Heads of 64 lanes keep both on the reference paged
   attention (not a ``paged_decode_attention`` in their lowered
   step); a third, untrained gateway with 8 kv heads of 128 lanes
   must have the kernel in it, one lowering for all its layers.
4. train   — ResNet-50 b256 bf16 through ``net.fit(steps_per_loop=4)``.

Weights and data are random, made from ``SEED``. The last stdout line
is one JSON object, ``{"ok": true, "device": {...}}``; everything else
is on earlier lines. Timings printed here are host-clock readings of
this one run — a smoke test, not a benchmark.
"""
import argparse
import gc
import json
import re
import sys
import time

import numpy as np

SEED = 20260926

GPT = dict(vocab_size=50257, hidden=768, n_layers=12, n_heads=12,
           max_len=1024, ffn_mult=8 / 3, tie_embeddings=True,
           compute_dtype="bfloat16")
LM_TRAIN = dict(batch=16, seq_len=1024, steps=6)
SERVE = dict(max_slots=16, block=16, max_new=64,
             prompt_lens=(17, 700, 45, 130, 333, 64, 512, 250))
#: over the paged kernel's dispatch line: kv heads in whole 8-row
#: tiles, a head of whole 128-lane tiles (ops/pallas_kernels.py)
GPT_WIDE = dict(vocab_size=8192, hidden=2048, n_layers=2, n_heads=16,
                n_kv_heads=8, max_len=1024, ffn_mult=2,
                tie_embeddings=True, compute_dtype="bfloat16")
SERVE_WIDE = dict(max_slots=16, block=16, max_new=48,
                  prompt_lens=(17, 700, 45, 130))
RESNET = dict(num_classes=1000, image=224, batch=256, steps=8,
              steps_per_loop=4, compute_dtype="bfloat16")
MULTICHIP = dict(n_in=784, width=2048, hidden_layers=4, n_out=10,
                 batch=512, steps=6, devices=4)


def log(msg):
    print(msg, flush=True)


def check(ok, msg):
    """A failed phase raises — also under ``python -O``, which would
    strip ``assert`` statements."""
    if not ok:
        raise AssertionError(msg)


def _falling(name, losses):
    check(all(np.isfinite(losses)), f"{name}: non-finite loss {losses}")
    check(losses[-1] < losses[0], f"{name}: loss did not fall {losses}")


# -- phase 1 ----------------------------------------------------------------
def phase_device(want_count):
    import importlib.metadata as md

    import jax
    import jaxlib

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.perf import compile_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"[device] {json.dumps(device)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={md.version('libtpu')} "
        f"python={sys.version.split()[0]}")
    log(f"[device] compile_cache_dir={compile_cache.cache_dir()} "
        f"native_available={native.available()}")
    if device["platform"] != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU, JAX found "
                         f"{device['platform']!r}")
    if device["count"] != want_count:
        raise SystemExit(f"chip_smoke needs {want_count} chip(s) here, "
                         f"JAX found {device['count']}")
    return device


# -- phase 2 ----------------------------------------------------------------
def _shapes(tree, keep_sharding=False):
    """The tree as ``ShapeDtypeStruct``s — what lowering needs of
    buffers a step has donated."""
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if keep_sharding else None), tree)


def kernels_in_step(net, x, y):
    """Pallas kernel names in the LOWERED train step (the program
    ``fit`` dispatches), read from its ``tpu_custom_call``s."""
    import jax
    text = net._train_step_fn.lower(
        _shapes(net.params), _shapes(net.opt_state), _shapes(net.state),
        _shapes(x), _shapes(y), None, None,
        jax.random.PRNGKey(0)).as_text()
    names = re.findall(r'kernel_name = "([^"]+)"', text)
    check(len(names) == text.count("tpu_custom_call"),
          "a tpu_custom_call without a kernel name")
    return names


def phase_train_lm(gpt=GPT, cfg=LM_TRAIN, expect_kernels=True):
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    model = CausalTransformerLM(seed=SEED % 1000, **gpt)
    net = model.init(seq_len=cfg["seq_len"])
    rng = np.random.default_rng(SEED)
    toks = rng.integers(0, gpt["vocab_size"],
                        (cfg["batch"], cfg["seq_len"] + 1)).astype(np.int32)
    x, y = toks[:, :-1], toks[:, 1:]
    losses, walls = [], []
    for _ in range(cfg["steps"]):
        t0 = time.perf_counter()
        net.fit(x, y)               # ends in float(loss): a device sync
        walls.append(time.perf_counter() - t0)
        losses.append(net.score_)
    step_s = float(np.median(walls[1:]))
    log(f"[train-lm] steps={cfg['steps']} tokens/step="
        f"{cfg['batch'] * cfg['seq_len']} first_loss={losses[0]:.4f} "
        f"last_loss={losses[-1]:.4f} compile_s={walls[0] - step_s:.2f} "
        f"step_s={step_s:.4f}")
    _falling("train-lm", losses)
    names = kernels_in_step(net, x, y)
    flash = [n for n in names if "flash" in n]
    norms = [n for n in names if "rms" in n or "_ln_" in n]
    log(f"[train-lm] tpu_custom_calls={len(names)} flash={len(flash)} "
        f"fused_norm={len(norms)} kernels={sorted(set(names))}")
    if expect_kernels:
        check(any("bwd" in n for n in flash)
              and any("bwd" not in n for n in flash),
              "flash attention fwd+bwd kernels missing from the step")
        check(any("bwd" in n for n in norms)
              and any("fwd" in n for n in norms),
              "fused norm fwd+bwd kernels missing from the step")
    return model, net


# -- phase 3 ----------------------------------------------------------------
#: widest next-token log-probability gap (nats) at which a fork between
#: the paged and the dense greedy decode counts as a near-tie
NEAR_TIE_NATS = 0.25


def check_paged_equals_dense(tag, net, paged, dense):
    """The repo's contract is token identity (tests: float32, CPU). In
    bf16 on the chip the 16-row paged step and the 1-row dense scan
    round their logits differently, so greedy decode may fork where
    two tokens are tied to within rounding. Identity is required up to
    the first fork, and the fork must BE such a tie: the teacher-forced
    training forward (``net.output`` on the common prefix) has to rate
    the two tokens within ``NEAR_TIE_NATS``. After a fork the two
    continuations are different texts and are not compared."""
    same = int((paged == dense).sum())
    if same == len(dense):
        log(f"[{tag}] paged vs dense generate(): {same}/{len(dense)} "
            "tokens identical")
        return
    i = int(np.argmax(paged != dense))
    probs = np.asarray(net.output(dense[None, :i]))[0, -1].astype(
        np.float64)
    gap = abs(np.log(probs[dense[i]]) - np.log(probs[paged[i]]))
    log(f"[{tag}] paged vs dense generate(): {same}/{len(dense)} "
        f"tokens identical, first fork at position {i} (dense "
        f"{dense[i]} p={probs[dense[i]]:.5f}, paged {paged[i]} "
        f"p={probs[paged[i]]:.5f}, gap {gap:.4f} nats, top-1 "
        f"{int(probs.argmax())})")
    check(gap <= NEAR_TIE_NATS,
          f"{tag}: paged decode left dense generate() at position {i} "
          f"by {gap:.3f} nats — not a rounding tie")


def kernels_in_decode_step(model, net, sched):
    """Pallas kernel names in the LOWERED ``serving.decode_step``."""
    text = sched._step_fn.lower(
        _shapes(model.decode_params(net)), _shapes(sched.pager.pool),
        *sched._step_feed_shapes()).as_text()
    return re.findall(r'kernel_name = "([^"]+)"', text)


def phase_serve(model, net, cfg=SERVE, tag="serve", paged_kernel=False):
    from deeplearning4j_tpu.perf import sentry
    from deeplearning4j_tpu.serving import ServingGateway

    rng = np.random.default_rng(SEED + 1)
    prompts = [rng.integers(0, model.vocab_size, t).astype(np.int32)
               for t in cfg["prompt_lens"]]
    n_new = cfg["max_new"]
    t0 = time.perf_counter()
    dense = np.asarray(model.generate(net, prompts[0][None],
                                      n_new=n_new))[0]
    log(f"[{tag}] dense generate() prompt={len(prompts[0])} "
        f"n_new={n_new} wall_s={time.perf_counter() - t0:.2f} "
        "(compile included)")

    gw = ServingGateway(model, net, max_slots=cfg["max_slots"],
                        block=cfg["block"])
    rep = gw.warmup()
    log(f"[{tag}] warmup compiled={rep['compiled']} "
        f"seconds={rep['seconds']:.2f} buckets={rep['buckets']}")
    paged = [n for n in kernels_in_decode_step(model, net, gw._sched)
             if "paged_decode" in n]
    log(f"[{tag}] paged_decode_attention lowerings in the decode "
        f"step: {len(paged)} (layers={model.n_layers})")
    check(len(paged) == (1 if paged_kernel else 0),
          f"{tag}: paged kernel {'missing from' if paged_kernel else 'in'}"
          " the lowered decode step")
    traces_before = sentry.total_traces()
    t0 = time.perf_counter()
    with sentry.strict():
        streams = [gw.submit(p, max_new=n_new,
                             tenant=("tenant-a", "tenant-b")[i % 2])
                   for i, p in enumerate(prompts)]
        outs = [st.result(timeout=300) for st in streams]
    wall = time.perf_counter() - t0
    retraces = sentry.total_traces() - traces_before
    for st, p, out in zip(streams, prompts, outs):
        check(st.error() is None, st.error())
        check(st.n_generated() == n_new, (len(p), st.n_generated()))
        check(len(out) == len(p) + n_new, "prompt not reattached")
        check(((0 <= out) & (out < model.vocab_size)).all(),
              "token id out of the vocabulary")
    pager = gw._sched.pager
    pager.check_invariants()
    check(pager.free_pages() == pager.n_pages - 1, "pages leaked")
    gw.shutdown()
    ttft = sorted(st.ttft_s for st in streams)
    log(f"[{tag}] requests={len(streams)}/{len(prompts)} complete "
        f"tokens_out={n_new * len(streams)} wall_s={wall:.2f} "
        f"ttft_s(min/max)={ttft[0]:.3f}/{ttft[-1]:.3f} "
        f"retraces_after_warmup={retraces} pager_invariants=ok")
    check(retraces == 0, f"{retraces} traces after warmup")
    check_paged_equals_dense(tag, net, outs[0], dense)


# -- phase 4 ----------------------------------------------------------------
def phase_train_resnet(cfg=RESNET):
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.train.listeners import CollectScoresListener
    from deeplearning4j_tpu.zoo import ResNet50

    size, batch = cfg["image"], cfg["batch"]
    # the zoo default (lr 0.1) assumes a warmup schedule; a handful of
    # steps from random weights want a rate that falls from step one
    net = ResNet50(num_classes=cfg["num_classes"], seed=SEED % 1000,
                   input_shape=(size, size, 3),
                   updater=upd.Nesterovs(learning_rate=0.01,
                                         momentum=0.9),
                   compute_dtype=cfg["compute_dtype"]).init()
    rng = np.random.default_rng(SEED + 2)
    x = rng.standard_normal((batch, size, size, 3), dtype=np.float32)
    y = np.eye(cfg["num_classes"], dtype=np.float32)[
        rng.integers(0, cfg["num_classes"], batch)]
    scores = CollectScoresListener()
    net.listeners.append(scores)
    k = cfg["steps_per_loop"]
    walls = []
    for _ in range(cfg["steps"] // k):      # one scanned loop per fit
        t0 = time.perf_counter()
        net.fit([(x, y)] * k, steps_per_loop=k)
        walls.append(time.perf_counter() - t0)
    losses = [s for _, s in scores.scores]
    check(len(losses) == cfg["steps"], losses)
    log(f"[train-resnet50] steps={len(losses)} batch={batch} "
        f"steps_per_loop={k} first_loss={losses[0]:.4f} "
        f"last_loss={losses[-1]:.4f} "
        f"compile_s={walls[0] - walls[-1]:.2f} "
        f"step_s={walls[-1] / k:.4f} (incl. host->device of the batch)")
    _falling("train-resnet50", losses)


# -- --multichip ------------------------------------------------------------
def _mlp(cfg):
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    # momentum SGD: there is optimizer state for ZeRO to shard, and
    # the update is linear in the gradient, so a last-bit difference
    # between two programs stays a last-bit difference (Adam's
    # m/sqrt(v) amplifies it wherever a gradient is ~0: chip run 3 of
    # PR 21 matched every loss and missed 30 of 1.6M weights)
    b = (NeuralNetConfiguration.builder().seed(SEED % 1000)
         .updater(upd.Nesterovs(learning_rate=0.01, momentum=0.9))
         .list())
    for _ in range(cfg["hidden_layers"]):
        b = b.layer(DenseLayer(n_out=cfg["width"], activation="tanh"))
    conf = (b.layer(OutputLayer(n_out=cfg["n_out"], activation="softmax",
                                loss="mcxent"))
            .set_input_type(InputType.feed_forward(cfg["n_in"])).build())
    return MultiLayerNetwork(conf).init()


def _batch_sharding(w, cfg):
    """How the wrapper's compiled step takes the feature batch."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.wrapper import WARMUP_FEEDS

    feed = WARMUP_FEEDS[w._step_builder](
        w, jax.ShapeDtypeStruct((cfg["batch"], cfg["n_in"]), jnp.float32),
        jax.ShapeDtypeStruct((cfg["batch"], cfg["n_out"]), jnp.float32),
        jax.random.PRNGKey(0))
    x_at = next(i for i, a in enumerate(feed)
                if isinstance(a, jax.ShapeDtypeStruct))
    compiled = w._step.lower(*[
        a if isinstance(a, jax.ShapeDtypeStruct)
        else _shapes(a, keep_sharding=True) for a in feed]).compile()
    return compiled.input_shardings[0][x_at]


def phase_multichip(cfg=MULTICHIP):
    """ParallelWrapper SYNC (replicated, and ZeRO ``sharded_update``)
    over a 4-device data mesh against the same steps of the same net
    on one device — the tolerance of tests/test_sharded_update.py."""
    import jax

    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.train.listeners import CollectScoresListener

    n = cfg["devices"]
    rng = np.random.default_rng(SEED + 3)
    rows = cfg["batch"] * cfg["steps"]
    x = rng.standard_normal((rows, cfg["n_in"]), dtype=np.float32)
    y = np.eye(cfg["n_out"], dtype=np.float32)[
        (x[:, :cfg["n_out"]]).argmax(1)]
    ds = DataSet(x, y)

    def run(make_trainer):
        net = _mlp(cfg)
        scores = CollectScoresListener()
        net.listeners.append(scores)
        trainer = make_trainer(net)
        trainer.fit(ListDataSetIterator(ds, batch_size=cfg["batch"]))
        return net, trainer, [s for _, s in scores.scores]

    # the equivalence is about sharding, not about the MXU's default
    # bf16 passes for f32 operands: compare at full f32 precision
    with jax.default_matmul_precision("highest"):
        single, _, ref = run(lambda net: net)
        check(len(single.params["layer_0"]["W"].sharding.device_set) == 1,
              "the single-device reference is not on one device")
        log(f"[multichip] single-device losses={np.round(ref, 6).tolist()}")
        check(all(np.isfinite(ref)), ref)
        for sharded in (False, True):
            tag = "sync+sharded_update" if sharded else "sync"
            net, w, got = run(
                lambda net: ParallelWrapper.builder(net).workers(n)
                .sharded_update(sharded).build())
            check(w.mesh.devices.size == n, w.mesh)
            # params (and the ZeRO optimizer shards) live on all n
            # devices after the steps; the batch is split by the
            # step's own executable: rows/n on each of n devices
            placed = jax.tree.leaves(net.params)
            if sharded:
                placed += jax.tree.leaves(w._dp_state)
            on = {len(a.sharding.device_set) for a in placed}
            check(on == {n}, f"{tag}: arrays on {on} devices, want {n}")
            xs = _batch_sharding(w, cfg)
            check(len(xs.device_set) == n and xs.shard_shape(
                (cfg["batch"], cfg["n_in"]))[0] == cfg["batch"] // n, xs)
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7,
                                       err_msg=f"{tag} loss trajectory")
            for lname in single.params:
                for key in single.params[lname]:
                    np.testing.assert_allclose(
                        np.asarray(net.params[lname][key]),
                        np.asarray(single.params[lname][key]),
                        rtol=1e-4, atol=1e-6,
                        err_msg=f"{tag} {lname}/{key}")
            log(f"[multichip] {tag}: devices={n} arrays_on={sorted(on)} "
                f"losses={np.round(got, 6).tolist()} == single-device "
                "(rtol 1e-5), params rtol 1e-4")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the 4-chip ParallelWrapper path and "
                         "its single-device comparison")
    args = ap.parse_args()
    t_start = time.perf_counter()
    device = phase_device(MULTICHIP["devices"] if args.multichip else 1)
    if args.multichip:
        phase_multichip()
    else:
        model, net = phase_train_lm()
        phase_serve(model, net)
        # the float gateway serves in ~40 s cold (my chip run, PR 21):
        # cheap enough to drive the int8-KV pages too
        from deeplearning4j_tpu.zoo import CausalTransformerLM
        phase_serve(CausalTransformerLM(cache_quant="int8", **GPT), net,
                    tag="serve-int8kv")
        wide = CausalTransformerLM(seed=SEED % 1000, **GPT_WIDE)
        phase_serve(wide, wide.init(), cfg=SERVE_WIDE, tag="serve-wide",
                    paged_kernel=True)
        del model, net, wide
        gc.collect()
        phase_train_resnet()
    from deeplearning4j_tpu.perf import compile_cache
    log(f"[cache] {json.dumps(compile_cache.cache_stats())}")
    log(f"[done] wall_s={time.perf_counter() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

"""Collective-volume accounting for the multi-chip scaling claim
(BASELINE #5 "linear to 32 chips"; VERDICT r2 #10).

Compiles representative distributed train steps on the virtual
8-device CPU mesh, extracts every collective op and its byte volume
from the optimized HLO, and projects per-step ICI time at v5e link
bandwidth against MXU compute time — the derisking evidence for the
scaling claim until real multi-chip hardware is reachable.

Wire-volume model (ring algorithms, per device):
  all-reduce      2·N·(n−1)/n     (reduce-scatter + all-gather)
  all-gather      S·(n−1)         (S = per-device shard bytes sent)
  reduce-scatter  (N/n)·(n−1)
  collective-permute  N           (one neighbor hop)
  all-to-all      N·(n−1)/n

    python tools/collective_volume.py [--markdown]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# CPU-only by nature: the collective byte volumes are read from HLO
# compiled for 8 VIRTUAL devices — a static analysis that needs no
# chip and must not take one from the process that holds it
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# the HLO collective walker now lives in the communication
# observatory; this tool is a thin analytic front-end over it
from deeplearning4j_tpu.obs import commtime as _commtime  # noqa: E402

# the projection is FOR a v5e, from the one peaks table (2D torus;
# ring collectives ride one link direction per neighbor hop)
from deeplearning4j_tpu.environment import DEVICE_PEAKS  # noqa: E402

V5E_ICI_GBPS = DEVICE_PEAKS["TPU v5 lite"]["ici_gbs"] * 1e9

def collectives_of(compiled, n_devices=8):
    """Parse optimized HLO → [(kind, tensor_bytes, wire_bytes)].

    Delegates to :func:`obs.commtime.collective_records`;
    ``uniform_ring=n_devices`` pins the legacy analytic model (every
    ring sized to the full mesh) so the BASELINE rows stay put.
    Collectives inside a `while` body (the ring attention fori_loop)
    execute once per trip; the ring's trip count is the mesh size, so
    those are multiplied by ``n_devices``.
    """
    return [(r["kind"], r["tensor_bytes"], r["wire_bytes"])
            for r in _commtime.collective_records(
                compiled.as_text(), uniform_ring=n_devices)]


# re-exported from the observatory (the walker's canonical home)
parse_replica_groups = _commtime.parse_replica_groups


def axis_groups(mesh_axes):
    """Expected replica-group partition for every non-empty subset of
    mesh axes: {axes_tuple: frozenset of frozensets}. ``mesh_axes`` is
    an ordered dict-like of axis name → size with MAJOR-first device
    numbering (the ``make_mesh`` convention)."""
    names = list(mesh_axes)
    sizes = [mesh_axes[n] for n in names]
    ids = np.arange(int(np.prod(sizes))).reshape(sizes)
    out = {}
    from itertools import combinations
    for r in range(1, len(names) + 1):
        for subset in combinations(range(len(names)), r):
            other = [i for i in range(len(names)) if i not in subset]
            moved = ids.transpose(list(other) + list(subset)).reshape(
                -1, int(np.prod([sizes[i] for i in subset])))
            out[tuple(names[i] for i in subset)] = frozenset(
                frozenset(int(d) for d in row) for row in moved)
    return out


def collectives_with_axes(compiled, mesh_axes):
    """[(kind, tensor_bytes, axes_or_None, in_while)] for every
    collective in the optimized HLO — ``axes`` is the mesh-axis subset
    whose group partition matches the op's replica groups (None when
    the groups don't align to axes, e.g. a point-to-point permute's
    source-target pairs; collective-permute reports the axes whose
    subgrid contains every source→target hop instead)."""
    expected = axis_groups(mesh_axes)
    out = []
    for r in _commtime.collective_records(compiled.as_text()):
        axes = None
        if r["kind"] == "collective-permute":
            pairs = r["source_target_pairs"]
            if pairs:
                for ax, part in expected.items():
                    by = {frozenset(g) for g in part}
                    if all(any(s in g and t in g for g in by)
                           for s, t in pairs):
                        axes = ax
                        break
        else:
            groups = r["replica_groups"]
            if groups is not None:
                for ax, part in expected.items():
                    if groups == part:
                        axes = ax
                        break
        out.append((r["kind"], r["tensor_bytes"], axes, r["in_while"]))
    return out


def composed_lm(mesh_devices=8):
    """Composed DP×SP×TP causal-LM train step on one
    {"data":2, "seq":2, "tensor":N//4} mesh (dryrun stage 7 /
    tests/test_composed_parallel.py workload) — for the per-axis
    collective gates."""
    from deeplearning4j_tpu.parallel import (
        composed_context, composed_data_sharding, make_mesh,
        shard_lm_for_composed)
    from deeplearning4j_tpu.zoo import CausalTransformerLM

    model = CausalTransformerLM(
        vocab_size=64, hidden=32, n_layers=2, n_heads=2, max_len=32,
        ffn_mult=2.0, tie_embeddings=True, sequence_parallel="ring",
        seed=7)
    net = model.init(seq_len=32)
    mesh = make_mesh({"data": 2, "seq": 2,
                      "tensor": mesh_devices // 4})
    shard_lm_for_composed(net, mesh, tensor_axis="tensor")
    ds = composed_data_sharding(mesh)
    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32), ds)
    y = jax.device_put(
        jnp.asarray(rng.integers(0, 64, (4, 32)), jnp.int32), ds)
    step = net._make_train_step()
    args = (net.params, net.opt_state, net.state, x, y, None, None,
            jax.random.PRNGKey(0))
    return step, args, composed_context(mesh), dict(
        data=2, seq=2, tensor=mesh_devices // 4)


def analyze(name, jitted, args, n_devices=8):
    """HLO-derived collective counts + wire bytes + projected ICI time.

    No compute-time column here: XLA-CPU cost analysis is meaningless
    for TPU projection, and a projected ICI time is not a measurement
    — per-step times come from a chip run.
    """
    compiled = jitted.lower(*args).compile()
    colls = collectives_of(compiled, n_devices)
    wire = sum(w for _, _, w in colls)
    by_kind = {}
    for kind, _, w in colls:
        c, tot = by_kind.get(kind, (0, 0.0))
        by_kind[kind] = (c + 1, tot + w)
    t_ici = wire / V5E_ICI_GBPS
    # per-scope wire account through the observatory's metadata join
    # (group-sized rings, so composed meshes may differ from the
    # uniform-ring analytic column — that is the point)
    led = _commtime.wire_ledger([compiled], n_devices=n_devices)
    return {"name": name, "collectives": by_kind,
            "wire_bytes": wire, "t_ici_ms": t_ici * 1e3,
            "by_scope": {k: round(v["wire_bytes"] / 1e6, 3)
                         for k, v in sorted(led["by_scope"].items())}}


# ---------------------------------------------------------------------------
# representative configs (mirror __graft_entry__.dryrun_multichip stages)
# ---------------------------------------------------------------------------
def dp_resnet(mesh_devices=8, sharded=True):
    """DP ResNet-50 sync step: the BASELINE #5 workload. Collective
    volume = one gradient all-reduce of every parameter.

    ``sharded=False`` compiles the SAME step with the batch replicated
    — the classic lost-sharding regression; the CI gate uses it as the
    detection canary (no gradient all-reduce is emitted)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import optax
    from deeplearning4j_tpu.zoo import ResNet50
    from deeplearning4j_tpu.nn import updaters as upd

    mesh = Mesh(np.array(jax.devices()[:mesh_devices]), ("data",))
    net = ResNet50(num_classes=1000, seed=0, input_shape=(64, 64, 3),
                   updater=upd.Nesterovs(learning_rate=0.1,
                                         momentum=0.9)).init()
    x = jnp.zeros((16, 64, 64, 3), jnp.float32)
    y = jnp.zeros((16, 1000), jnp.float32)
    rng = jax.random.PRNGKey(0)
    repl = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("data"))

    def step(params, opt_state, state, x, y):
        (loss, new_state), g = jax.value_and_grad(
            net._loss_fn, has_aux=True)(params, state,
                                        {net.conf.inputs[0]: x}, [y],
                                        {}, {}, rng)
        updates, opt_state = net._optimizer.update(g, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, new_state, loss

    dshard = shard if sharded else repl
    jitted = jax.jit(step,
                     in_shardings=(repl, repl, repl, dshard, dshard),
                     out_shardings=(repl, repl, repl, repl))
    return jitted, (net.params, net.opt_state, net.state, x, y)


def dp_sharded_wrapper(mesh_devices=8, sharded_update=True):
    """ParallelWrapper SYNC step with the ZeRO sharded weight update
    (or the replicated baseline with ``sharded_update=False``): the
    gradient sync becomes per-leaf reduce-scatter + param all-gather,
    and the optimizer-state footprint drops to 1/N per device.
    Returns ``(jitted_step, args, accounting)`` — accounting carries
    the per-device optimizer/param/grad byte model the CI gate asserts
    against the HLO."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.parallel import (ParallelWrapper,
                                             per_device_bytes)

    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(upd.Adam(learning_rate=1e-3)).list()
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=16, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(64)).build())
    net = MultiLayerNetwork(conf).init()
    w = ParallelWrapper(net, workers=mesh_devices,
                        sharded_update=sharded_update)
    w._prepare()
    dshard = NamedSharding(w.mesh, P("data"))
    b = 8 * mesh_devices
    x = jax.device_put(jnp.zeros((b, 64), jnp.float32), dshard)
    y = jax.device_put(jnp.zeros((b, 16), jnp.float32), dshard)
    rng = jax.random.PRNGKey(0)
    if sharded_update:
        args = (net.params, w._dp_state, net.state, x, y, rng)
    else:
        args = (net.params, net.opt_state, net.state, x, y, rng)
    p_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize
                  for p in jax.tree.leaves(net.params))
    acct = {
        "param_bytes": p_bytes,
        "grad_bytes": p_bytes,           # f32 grads mirror f32 params
        "opt_bytes_replicated_per_device":
            per_device_bytes(net.opt_state),
        "opt_bytes_per_device":
            per_device_bytes(w._dp_state, mesh_devices)
            if sharded_update else per_device_bytes(net.opt_state),
    }
    return w._step, args, acct


def encoded_wrapper(mesh_devices=8):
    """ParallelWrapper ENCODED step (same MLP geometry as
    ``dp_sharded_wrapper``): threshold-encode per shard, exchange,
    decode. The plain encoded exchange psums the DECODED f32
    gradients — DENSE wire volume on the wire; the measured-vs-dense
    column this row feeds is the honest number the ROADMAP item-4
    packed exchange (1-bit words all-gathered, ~16x less) must beat.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.parallel import ParallelWrapper

    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(upd.Adam(learning_rate=1e-3)).list()
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=16, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(64)).build())
    net = MultiLayerNetwork(conf).init()
    w = ParallelWrapper(net, workers=mesh_devices,
                        mode=ParallelWrapper.ENCODED)
    w._prepare()
    dshard = NamedSharding(w.mesh, P("data"))
    b = 8 * mesh_devices
    x = jax.device_put(jnp.zeros((b, 64), jnp.float32), dshard)
    y = jax.device_put(jnp.zeros((b, 16), jnp.float32), dshard)
    rng = jax.random.PRNGKey(0)
    args = (net.params, net.opt_state, net.state, w._dp_state, x, y,
            rng)
    return w._step, args


def tp_mlp(mesh_devices=8):
    """Tensor-parallel 2-layer MLP (col→row sharded): all-reduce of
    activations, not params."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:mesh_devices]), ("model",))
    d, h = 1024, 4096
    params = {"W1": jnp.zeros((d, h), jnp.bfloat16),
              "W2": jnp.zeros((h, d), jnp.bfloat16)}
    x = jnp.zeros((32, d), jnp.bfloat16)
    shardings = {"W1": NamedSharding(mesh, P(None, "model")),
                 "W2": NamedSharding(mesh, P("model", None))}

    def fwd(p, x):
        hdn = jax.nn.relu(x @ p["W1"])
        return jnp.sum((hdn @ p["W2"]) ** 2)

    def step(p, x):
        return jax.value_and_grad(fwd)(p, x)

    jitted = jax.jit(step,
                     in_shardings=({"W1": shardings["W1"],
                                    "W2": shardings["W2"]},
                                   NamedSharding(mesh, P())))
    return jitted, (jax.device_put(params, shardings), x)


def sp_ring(mesh_devices=8, t_total=8192):
    """Ring-attention fwd+bwd: collective-permute KV/mask blocks per
    ring step (the long-context SP path)."""
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.parallel.ring_attention import \
        ring_self_attention
    mesh = make_mesh({"seq": mesh_devices})
    b, h, d = 1, 8, 128
    q = jnp.zeros((b, t_total, h, d), jnp.bfloat16)

    def loss(q):
        return jnp.sum(
            ring_self_attention(q, q, q, mesh, causal=True)
            .astype(jnp.float32) ** 2)

    jitted = jax.jit(jax.value_and_grad(loss))
    return jitted, (q,)


def _try_row(rows, name, build_and_analyze):
    """One table row, or a visibly-skipped placeholder when the
    config needs a capability this environment lacks (the ring
    attention path wants ``jax.typeof``) — a broken config must not
    take down the other rows' evidence."""
    try:
        row = build_and_analyze()
    except Exception as e:
        row = {"name": name, "collectives": {}, "wire_bytes": 0.0,
               "t_ici_ms": 0.0, "by_scope": {},
               "skipped": f"{type(e).__name__}: {e}"}
    rows.append(row)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()

    rows = []
    for name, build in [("DP ResNet-50 (grad allreduce)", dp_resnet),
                        ("TP MLP col→row (activation allreduce)",
                         tp_mlp),
                        ("SP ring attention T=8k causal", sp_ring)]:
        _try_row(rows, name,
                 lambda name=name, build=build: analyze(
                     name, *build()[:2]))
    # ZeRO-DP sharded weight update: reduce-scatter + all-gather
    # replace the gradient allreduce at identical ring wire volume
    _try_row(rows, "ZeRO-DP MLP (sharded weight update)",
             lambda: analyze("ZeRO-DP MLP (sharded weight update)",
                             *dp_sharded_wrapper()[:2]))
    # dense DP baseline on the SAME model — the comparator the
    # encoded row is measured against
    dense = _try_row(
        rows, "DP MLP dense baseline (replicated update)",
        lambda: analyze("DP MLP dense baseline (replicated update)",
                        *dp_sharded_wrapper(sharded_update=False)[:2]))
    # encoded-gradient exchange (ROADMAP item 4's measurement bed):
    # measured wire vs the dense baseline, through the ledger API
    enc = _try_row(
        rows, "Encoded DP MLP (ParallelWrapper ENCODED)",
        lambda: analyze("Encoded DP MLP (ParallelWrapper ENCODED)",
                        *encoded_wrapper()))
    if not enc.get("skipped") and dense["wire_bytes"]:
        enc["vs_dense"] = enc["wire_bytes"] / dense["wire_bytes"]

    def _composed():
        step, a, ctx, _axes = composed_lm()
        with ctx:   # compiled under its ambient context
            return analyze("Composed DP×SP×TP causal-LM step", step, a)

    _try_row(rows, "Composed DP×SP×TP causal-LM step", _composed)

    if args.markdown:
        print("| config | collectives (count × kind) | wire MB/step "
              "| projected ICI ms (45 GB/s link) | vs dense |")
        print("|---|---|---|---|---|")
        for r in rows:
            if r.get("skipped"):
                print(f"| {r['name']} | skipped: {r['skipped']} "
                      "| — | — | — |")
                continue
            kinds = ", ".join(f"{c}× {k}"
                              for k, (c, _) in sorted(
                                  r["collectives"].items()))
            vs = (f"{r['vs_dense']:.2f}×"
                  if r.get("vs_dense") is not None else "—")
            print(f"| {r['name']} | {kinds} "
                  f"| {r['wire_bytes'] / 1e6:.1f} "
                  f"| {r['t_ici_ms']:.2f} | {vs} |")
    else:
        for r in rows:
            print(r)


if __name__ == "__main__":
    main()

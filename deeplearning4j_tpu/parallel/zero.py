"""ZeRO-style sharded weight update — flat shard layout + accounting.

Reference: "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (arxiv 2004.13336). The replicated
data-parallel step all-reduces gradients and then has every replica
redo the SAME optimizer math over the SAME full parameter set, holding
N copies of the optimizer moments. The sharded update replaces that
with: reduce-scatter the gradients (each replica receives the mean of
its 1/N slice), apply the optimizer to the local slice only — against
optimizer state that lives permanently as 1/N shards — and all-gather
the updated parameters for the next forward. Wire volume is identical
to the all-reduce it replaces (a ring all-reduce IS a reduce-scatter +
all-gather); optimizer-state HBM and update FLOPs drop by N.

:class:`FlatShardLayout` is the layout half: every parameter leaf
viewed as a flat vector, zero-padded to a multiple of the replica
count so ``lax.psum_scatter``/``lax.all_gather`` tile evenly. The
layout keeps the parameter pytree structure (one flat leaf per
original leaf), so per-layer optimizer partitioning
(``optax.multi_transform`` keyed by layer name) keeps working on
shards unchanged. Elementwise optimizer transforms (every stock
updater: Adam/AdamW/SGD/momentum/RMSProp/...) are exact on shards;
cross-element gradient normalization (per-layer / global-norm
clipping) is not expressible shard-locally and is rejected up front by
``ParallelWrapper``.

``zero_dp_report`` is the measurement half: the before/after row
(step time, per-device optimizer-state bytes, estimated peak-HBM
delta) printed by the 8-virtual-device dry run
(``__graft_entry__.dryrun_multichip``) — a CPU measurement of bytes
and agreement, never a device time.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from jax.lax import all_gather, psum_scatter


class FlatShardLayout:
    """Per-leaf flat shard layout over ``n_shards`` replicas.

    Host-side metadata is fixed at construction from a donor params
    pytree; the ``flatten``/``shard``/``scatter_mean``/``gather``
    methods are traced inside the SPMD step. All methods preserve the
    donor treedef, so optimizer label trees and per-layer diagnostics
    keep addressing leaves the same way.
    """

    def __init__(self, params, n_shards: int):
        import jax
        import numpy as np

        self.n = int(n_shards)
        leaves, self.treedef = jax.tree_util.tree_flatten(params)
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.padded = [((s + self.n - 1) // self.n) * self.n
                       for s in self.sizes]

    # -- traced pieces ------------------------------------------------------
    def flatten(self, tree):
        """Params-like tree -> same-structure tree of flat zero-padded
        ``(padded,)`` leaves."""
        import jax
        import jax.numpy as jnp

        leaves = jax.tree_util.tree_leaves(tree)
        flat = [jnp.pad(jnp.ravel(l), (0, p - s))
                for l, s, p in zip(leaves, self.sizes, self.padded)]
        return jax.tree_util.tree_unflatten(self.treedef, flat)

    def unflatten(self, flat_tree):
        """Inverse of :meth:`flatten` (drops the zero pad)."""
        import jax

        flats = jax.tree_util.tree_leaves(flat_tree)
        leaves = [f[:s].reshape(shape) for f, s, shape in
                  zip(flats, self.sizes, self.shapes)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def shard(self, flat_tree, index):
        """This replica's ``(padded/n,)`` slice of every flat leaf."""
        import jax
        from jax import lax

        flats = jax.tree_util.tree_leaves(flat_tree)
        out = [lax.dynamic_slice(f, (index * (p // self.n),),
                                 (p // self.n,))
               for f, p in zip(flats, self.padded)]
        return jax.tree_util.tree_unflatten(self.treedef, out)

    def scatter_mean(self, tree, axis_name: str):
        """Reduce-scatter a (grads-like) tree: each replica receives
        the cross-replica MEAN of its flat slice — the sharded
        equivalent of the replicated path's gradient ``pmean``
        (bit-identical on power-of-two meshes: scatter-sum and
        all-reduce-sum accumulate in the same order, and the ``/n`` is
        an exact power-of-two scale)."""
        import jax

        from deeplearning4j_tpu.obs import devtime

        # devtime scope: names the ZeRO reduce-scatter phase's device
        # time (trace-time HLO metadata only)
        with devtime.scope("zero.reduce_scatter"):
            flat = self.flatten(tree)
            return jax.tree.map(
                lambda f: psum_scatter(f, axis_name, tiled=True)
                / self.n,
                flat)

    def gather(self, shard_tree, axis_name: str):
        """All-gather per-replica shards back into the original-shape
        tree (every replica receives identical full leaves — the ZeRO
        lockstep invariant the param-divergence fence asserts)."""
        import jax

        from deeplearning4j_tpu.obs import devtime

        # devtime scope: names the ZeRO param all-gather phase.
        # ParallelWrapper(gather_overlap=True) moves this gather to
        # the TOP of the next step so it overlaps that step's forward
        # (ISSUE 15 tentpole c — measured by zero_dp_report's
        # sharded_overlap row); the scope covers both placements
        with devtime.scope("zero.all_gather"):
            full = jax.tree.map(
                lambda s: all_gather(s, axis_name, tiled=True),
                shard_tree)
            return self.unflatten(full)

    # -- host-side helpers --------------------------------------------------
    def shard_structs(self):
        """Abstract per-replica shard tree (warmup donors)."""
        import jax

        return jax.tree_util.tree_unflatten(
            self.treedef,
            [jax.ShapeDtypeStruct((p // self.n,), d)
             for p, d in zip(self.padded, self.dtypes)])


class LayoutMismatch(ValueError):
    """A checkpoint's flat leaves do not belong to the target
    parameter layout (non-zero data where the zero pad must be, or an
    un-re-paddable shape). Raised by :func:`repad_flat_leaves`;
    restore chains treat it as FAIL-FAST configuration error, never as
    corruption — quarantining would walk the fallback chain and move
    aside every (perfectly valid) checkpoint of the mismatched net."""


def repad_flat_leaves(src_leaves, ref_leaves, *, strict: bool = True):
    """Re-pad flat-layout leaves written under ONE shard count onto
    the padded sizes of ANOTHER — the re-scatter half of resharded
    restore (``ShardedCheckpointer.restore_wrapper(reshard=True)``).

    A flat leaf padded for N devices and the same leaf padded for M
    devices differ only in the zero tail (``ceil(s/N)*N`` vs
    ``ceil(s/M)*M`` beyond the true size ``s``), and the zero pad is
    an *invariant of training*: padded gradient lanes are identically
    0, so every elementwise optimizer keeps moments and params exactly
    0 there. Truncate-or-extend with zeros is therefore bit-exact on
    the real content. ``strict`` verifies the invariant — any
    truncated tail must be all-zero — so a mismatched layout (wrong
    net for this checkpoint) fails loudly instead of silently
    dropping state. Scalar/replicated leaves (optimizer step counts)
    pass through unchanged. Host-side (numpy): runs once per restore,
    before device placement."""
    import numpy as np

    out = []
    for i, (cur, want) in enumerate(zip(src_leaves, ref_leaves)):
        cur = np.asarray(cur)
        wshape = tuple(want.shape)
        if tuple(cur.shape) == wshape:
            out.append(cur)
            continue
        if cur.ndim != 1 or len(wshape) != 1:
            raise LayoutMismatch(
                f"resharded restore: leaf {i} has shape {cur.shape} "
                f"but the target layout wants {wshape} — only flat "
                "(1-D padded) leaves can be re-padded")
        n = int(wshape[0])
        if cur.size > n:
            tail = cur[n:]
            if strict and np.any(tail != 0):
                raise LayoutMismatch(
                    f"resharded restore: leaf {i} carries non-zero "
                    f"data beyond the target padded size {n} "
                    f"({cur.size} > {n}) — the checkpoint does not "
                    "match this parameter layout")
            cur = cur[:n]
        elif cur.size < n:
            cur = np.pad(cur, (0, n - cur.size))
        out.append(cur.astype(want.dtype))
    return out


def sharded_leaf(leaf, n_shards: int) -> bool:
    """Is this optimizer-state leaf carried as 1/N shards under the
    flat layout? Moment trees mirror the flat param leaves — vectors
    padded to a multiple of the shard count; scalars (step counts,
    schedule state) stay replicated."""
    return leaf.ndim >= 1 and leaf.shape[0] % n_shards == 0


def per_device_bytes(tree, n_shards: int = 1) -> int:
    """Bytes of a pytree resident on ONE device: with ``n_shards > 1``
    the sharded leaves count at 1/N (their global array is laid out
    ``P('data')`` across the mesh), replicated scalars at full size."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        nb = size * leaf.dtype.itemsize
        if n_shards > 1 and sharded_leaf(leaf, n_shards):
            nb //= n_shards
        total += nb
    return int(total)


# ---------------------------------------------------------------------------
# before/after measurement row (bench.py / perf_dossier / MULTICHIP gate)
# ---------------------------------------------------------------------------

def zero_dp_report(n_devices: Optional[int] = None, steps: int = 10,
                   hidden: int = 256, features: int = 64,
                   classes: int = 8) -> Dict[str, Any]:
    """Replicated vs sharded-update SYNC row on the live device set:
    per-step wall time, per-device optimizer-state bytes, and an
    estimated peak-HBM (params + grads + moments) per device, plus a
    trajectory cross-check between the two modes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.nn import (MultiLayerNetwork,
                                       NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.config import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn import updaters as upd
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    n = int(n_devices or len(jax.devices()))
    if len(jax.devices()) < n or n < 2:
        return {"skipped": True,
                "reason": f"needs {n} devices, have {len(jax.devices())}"}

    def mk_net():
        conf = (NeuralNetConfiguration.builder().seed(7)
                .updater(upd.Adam(learning_rate=1e-3)).list()
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(DenseLayer(n_out=hidden, activation="relu"))
                .layer(OutputLayer(n_out=classes, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(features))
                .build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    batch = 8 * n
    x = rng.normal(size=(batch, features)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[
        rng.integers(0, classes, batch)]

    def drive(sharded: bool, overlap: bool = False) -> Dict[str, Any]:
        net = mk_net()
        w = ParallelWrapper(net, workers=n, sharded_update=sharded,
                            gather_overlap=overlap)
        it = ListDataSetIterator(DataSet(x, y), batch_size=batch)
        w.fit(it, epochs=2)               # build + warm the step
        t0 = obs.now()
        w.fit(it, epochs=steps)
        dt = (obs.now() - t0) / steps
        if sharded:
            opt_bytes = per_device_bytes(w._dp_state, n)
        else:
            opt_bytes = per_device_bytes(net.opt_state)
        p_bytes = per_device_bytes(net.params)
        return {"step_ms": round(dt * 1e3, 3),
                "opt_state_bytes_per_device": opt_bytes,
                # steady-state HBM model: master params + one gradient
                # tree + resident optimizer state, per device
                "est_peak_hbm_bytes_per_device":
                    2 * p_bytes + opt_bytes,
                "params": net.params}

    def max_rel(a_tree, b_tree) -> float:
        rel = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(a_tree),
                        jax.tree_util.tree_leaves(b_tree)):
            a, b = np.asarray(a), np.asarray(b)
            rel = max(rel, float(np.max(np.abs(a - b) /
                                        (np.abs(a) + 1e-6))))
        return rel

    rep = drive(False)
    sh = drive(True)
    # gather/forward overlap (ISSUE 15 tentpole c): the all-gather of
    # updated params moves to the top of the NEXT step so it overlaps
    # that step's forward — same math, reordered across the step
    # boundary (bit-identical to the end-gather sharded trajectory on
    # this mesh; measured so the dossier's zero_overlap row carries a
    # step-time delta, not a promise)
    ov = drive(True, overlap=True)
    # the trajectories are identical in exact arithmetic; XLA
    # compiles the programs with different fusion/FMA choices so
    # agreement is to float rounding, not bitwise
    rel = max_rel(rep["params"], sh["params"])
    rel_ov = max_rel(rep["params"], ov["params"])
    rep.pop("params")
    sh.pop("params")
    ov.pop("params")
    return {
        "n_devices": n,
        "platform": jax.devices()[0].platform,
        "model": f"mlp {features}-{hidden}-{hidden}-{classes} adam",
        "replicated": rep,
        "sharded": sh,
        "sharded_overlap": ov,
        "opt_state_ratio": round(
            sh["opt_state_bytes_per_device"]
            / max(1, rep["opt_state_bytes_per_device"]), 4),
        "step_time_ratio": round(
            sh["step_ms"] / rep["step_ms"], 3) if rep["step_ms"] > 0
            else None,
        "overlap_step_ratio": round(
            ov["step_ms"] / sh["step_ms"], 3) if sh["step_ms"] > 0
            else None,
        "max_param_rel_diff": rel,
        "max_param_rel_diff_overlap": rel_ov,
    }

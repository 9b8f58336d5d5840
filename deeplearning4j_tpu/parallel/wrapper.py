"""ParallelWrapper — single-process data parallelism over a device mesh.

Reference: ``org.deeplearning4j.parallelism.ParallelWrapper`` (+Builder,
DefaultTrainer/SymmetricTrainer, SURVEY §3.5): per-GPU replicas on
pinned threads exchanging averaged params or threshold-encoded
gradients through host memory.

TPU-native redesign: no threads, no replicas-as-objects, no host-memory
hops. One jitted SPMD train step over a ``Mesh``:

 - SYNC (default; ≙ reference SHARED_GRADIENTS without compression):
   batch sharded over the 'data' axis, params replicated; XLA inserts
   the ICI allreduce for the gradient mean. This is the mode that
   should win every benchmark.
 - SYNC + ``sharded_update=True`` (ZeRO-style, arxiv 2004.13336 /
   parallel/zero.py): same data parallelism, but the gradient
   ``pmean`` becomes a per-leaf flat ``psum_scatter``, the optimizer
   state lives on device only as 1/N shards (materialized directly
   sharded from the net's — possibly checkpoint-restored — opt
   state, whose replicated copy is then evicted to host memory),
   each replica updates only its slice, and an ``all_gather``
   rebuilds the full params for the next forward. Identical wire
   volume to the allreduce it replaces; optimizer-state HBM and
   update FLOPs drop by N.
 - ENCODED (≙ SHARED_GRADIENTS + EncodedGradientsAccumulator): explicit
   ``shard_map`` step; per-device grads go through threshold encoding
   with local residuals, the ternary updates are psum'd (what would
   cross DCN), residual state stays device-local.
 - AVERAGING (≙ ParallelWrapper averaging mode): independent per-device
   replicas (params carry a leading device axis), trained locally and
   ``pmean``-averaged every ``averaging_frequency`` iterations via
   lax.cond — divergence between averages matches the reference.
 - ASYNC (≙ SharedTrainingMaster's asynchronous gradient exchange):
   per-device replicas apply their own threshold-encoded update
   immediately and their peers' updates one step late
   (``EncodedGradientsAccumulator.exchange_async``) with residuals
   accumulating locally — the Hogwild-flavor DP the reference runs
   over Aeron, expressed as one SPMD step with carried in-flight
   state.

Every step variant shares one gradient helper (``_local_grads``) and
one update helper (``_apply_update``); every variant donates its full
carried state (params, optimizer state, layer state, accumulator
state) so XLA can reuse the buffers in place — and, for the sharded
update, overlap the parameter all-gather with the next step where the
schedule allows.
"""
from __future__ import annotations

import contextlib
import gc
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map
from deeplearning4j_tpu.parallel.zero import (FlatShardLayout,
                                              per_device_bytes)

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.parallel.compression import \
    EncodedGradientsAccumulator
from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh
from deeplearning4j_tpu.perf import sentry
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.policy import Preempted


@contextlib.contextmanager
def _gc_held():
    """Keep the cyclic garbage collector from running inside the block
    (and leave it as it was found). ``fit`` holds it from a step's
    launch until the next batch's copy is enqueued: the launch makes
    thousands of arrays, which set off a collection of the oldest
    generation every five or six steps, 100–135 ms in which the host
    stands still (as long as the step on the chips), so that the
    batch staged ahead missed its step (PERF.md §6, PR 27). Held
    through that burst, the step's arrays die young and are never
    promoted, and the full collections all but stop."""
    was_on = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_on:
            gc.enable()


def _replica_view(tree):
    """Strip the leading per-device axis a ``P('data')`` spec leaves on
    stacked replica state inside ``shard_map``."""
    return jax.tree.map(lambda a: a[0], tree)


def _stacked(tree):
    """Re-add the leading axis for a ``P('data')`` out spec."""
    return jax.tree.map(lambda a: a[None], tree)


#: gradient-normalization modes that reduce ACROSS a layer/tree —
#: not expressible on 1/N parameter shards (the shard-local norm is
#: not the layer norm); sharded_update rejects them up front
_CROSS_LEAF_GRAD_NORMS = frozenset({
    "clipl2perlayer", "clipl2perparamtype",
    "renormalizel2perlayer", "renormalizel2perparamtype"})


class ParallelWrapper:
    SYNC = "sync"
    ENCODED = "encoded"
    AVERAGING = "averaging"
    ASYNC = "async"

    def __init__(self, net, workers: Optional[int] = None,
                 mode: str = SYNC,
                 averaging_frequency: int = 5,
                 average_updaters: bool = True,
                 accumulator: Optional[EncodedGradientsAccumulator] = None,
                 mesh: Optional[Mesh] = None,
                 prefetch_buffer: int = 4,
                 sharded_update: bool = False,
                 gather_overlap: bool = False):
        self.net = net
        self.mesh = mesh or data_parallel_mesh(workers)
        self.n = int(np.prod(self.mesh.devices.shape))
        self.mode = mode
        self.averaging_frequency = averaging_frequency
        # reference ParallelWrapper.Builder#averageUpdaters (default
        # true): AVERAGING mode averages the optimizer moments along
        # with the params at every averaging round
        self.average_updaters = average_updaters
        self.accumulator = accumulator or (
            EncodedGradientsAccumulator()
            if mode in (self.ENCODED, self.ASYNC) else None)
        self.prefetch_buffer = prefetch_buffer
        if sharded_update and mode != self.SYNC:
            raise ValueError(
                "sharded_update is a SYNC-mode optimization (the "
                f"ZeRO weight-update sharding); mode {mode!r} carries "
                "per-replica state that is already not replicated")
        self.sharded_update = bool(sharded_update)
        # ZeRO gather/forward overlap (arxiv 2004.13336 §4, ROADMAP
        # item 3's PR 5 leftover): carry the param SHARDS between
        # steps and all-gather at the TOP of the next step, so XLA's
        # latency-hiding scheduler overlaps each leaf's gather with
        # the forward compute that does not yet need it. The plain
        # sharded step gathers at the END of the step, where the
        # gather serializes behind the whole update with nothing to
        # hide under. Trade: ``net.params`` refreshes when fit()
        # returns (and at every checkpoint_tree), not per step —
        # mid-fit listeners that read params directly see the
        # previous materialisation.
        if gather_overlap and not sharded_update:
            raise ValueError("gather_overlap rides the ZeRO sharded "
                             "update — set sharded_update=True")
        self.gather_overlap = bool(gather_overlap)
        self._pshard = None     # overlap mode: flat 1/N param shards
        self._params_stale = False
        self._pshard_src = None    # weakrefs of the leaves _pshard
        self._flatten_jit = None   # cached flatten/unflatten programs
        self._unflatten_jit = None
        self._step = None
        self._step_builder = None
        self._dp_state = None  # mode-specific device state
        # fit: the loss of the step on the chips that no one has read
        # yet, one deep; None when fit returns or raises
        self._flight = None
        self._shard_layout = None
        # MultiLayerNetwork takes (x, y); ComputationGraph takes
        # ({name: x}, [y]) — adapt here so every mode's step body can
        # stay network-agnostic. Multi-input/multi-output graphs pass
        # through as pytrees (list of features / list of labels — every
        # leaf is sharded over the data axis), matching the reference
        # ParallelWrapper's support for arbitrary ComputationGraphs.
        if hasattr(net.conf, "inputs"):
            ins = net.conf.inputs

            def _graph_loss(p, s, x, y, rng, stats=None):
                xd = x if isinstance(x, dict) else (
                    dict(zip(ins, x)) if isinstance(x, (list, tuple))
                    else {ins[0]: x})
                yl = list(y) if isinstance(y, (list, tuple)) else [y]
                return net._loss_fn(p, s, xd, yl, {}, {}, rng,
                                    act_stats=stats)

            self._loss = _graph_loss
        else:
            self._loss = lambda p, s, x, y, rng, stats=None: \
                net._loss_fn(p, s, x, y, None, None, rng,
                             act_stats=stats)
        self._diag_step = None      # numerics diagnostic step (SYNC)
        self._diag_step_monitor = None   # monitor it was built for
        self._diag_unsupported_warned = False
        #: optional ``resilience.elastic.ElasticContext`` — when set,
        #: every step is stamped with the mesh epoch (stragglers from
        #: an old generation raise instead of corrupting collectives)
        #: and the blocking loss sync runs under the collective
        #: watchdog; ``None`` costs one branch per step
        self.elastic = None

    # -- builder parity (reference ParallelWrapper.Builder) -------------
    class Builder:
        def __init__(self, net):
            self._kw = {"net": net}

        def workers(self, n):
            self._kw["workers"] = n
            return self

        def training_mode(self, mode):
            self._kw["mode"] = mode
            return self

        def averaging_frequency(self, k):
            self._kw["averaging_frequency"] = k
            return self

        def average_updaters(self, flag: bool):
            self._kw["average_updaters"] = flag
            return self

        def sharded_update(self, flag: bool = True):
            self._kw["sharded_update"] = flag
            return self

        def gather_overlap(self, flag: bool = True):
            self._kw["gather_overlap"] = flag
            return self

        def gradients_accumulator(self, acc):
            self._kw["accumulator"] = acc
            # an accumulator implies an encoded-family mode; a prior
            # explicit ASYNC choice is kept, anything else (including
            # an explicit SYNC/AVERAGING, which cannot consume an
            # accumulator) becomes ENCODED — reference Builder behavior
            if self._kw.get("mode") not in (ParallelWrapper.ENCODED,
                                            ParallelWrapper.ASYNC):
                self._kw["mode"] = ParallelWrapper.ENCODED
            return self

        def prefetch_buffer(self, k):
            self._kw["prefetch_buffer"] = k
            return self

        def build(self):
            return ParallelWrapper(**self._kw)

    @staticmethod
    def builder(net) -> "ParallelWrapper.Builder":
        return ParallelWrapper.Builder(net)

    # -- shared step pieces (every variant composes these) ---------------
    def _local_grads(self, params, state, x, y, rng, want_stats=False):
        """Loss + gradients of this replica's (or the global) batch.
        With ``want_stats`` the activation taps of the numerics
        observatory ride the same forward (diagnostic steps only — the
        plain variants trace without them so the default program stays
        byte-identical)."""
        if not want_stats:
            (loss, new_state), grads = jax.value_and_grad(
                self._loss, has_aux=True)(params, state, x, y, rng)
            return loss, new_state, grads, None

        def lf(p):
            stats = {}
            loss, new_state = self._loss(p, state, x, y, rng, stats)
            return loss, (new_state, stats)

        (loss, (new_state, stats)), grads = jax.value_and_grad(
            lf, has_aux=True)(params)
        return loss, new_state, grads, stats

    def _apply_update(self, params, opt_state, grads, constrain=True):
        """One optimizer application: update, apply, (optionally)
        constrain. ``constrain=False`` for flat parameter shards —
        constraints are per-layer reductions and run on the gathered
        full tree instead."""
        from deeplearning4j_tpu import obs
        net = self.net
        # devtime scope: one annotation covers every wrapper variant's
        # optimizer phase (trace-time HLO metadata only)
        with obs.devtime.scope("optimizer.update"):
            updates, opt_state = net._optimizer.update(grads,
                                                       opt_state,
                                                       params)
            params = optax.apply_updates(params, updates)
            if constrain:
                params = net._apply_constraints(params)
        return params, opt_state, updates

    # -- ZeRO sharded-update plumbing ------------------------------------
    def _layout(self) -> FlatShardLayout:
        if self._shard_layout is None:
            self._shard_layout = FlatShardLayout(self.net.params,
                                                 self.n)
        return self._shard_layout

    def _check_sharded_update_supported(self):
        gn = getattr(self.net.conf, "gradient_normalization", None)
        if gn and str(gn).lower() in _CROSS_LEAF_GRAD_NORMS:
            raise ValueError(
                f"sharded_update applies the optimizer to 1/{self.n} "
                f"parameter shards; gradient normalization {gn!r} "
                "reduces across a whole layer/tree and would see only "
                "the local shard — use sharded_update=False, or "
                "elementwise clipping (ClipElementWiseAbsoluteValue)")
        if self.gather_overlap and self._net_has_constraints():
            raise ValueError(
                "gather_overlap defers the post-update param gather "
                "to the NEXT step's forward, so per-layer constraints "
                "(full-tree reductions after the update) have no "
                "gathered tree to run on — use gather_overlap=False "
                "with constrained layers")

    def _net_has_constraints(self) -> bool:
        """Does any layer carry post-update constraints? Walks the
        same objects ``_apply_constraints`` walks for each net type
        (MultiLayerNetwork ``layers``; ComputationGraph layer
        nodes)."""
        net = self.net
        layers = getattr(net, "layers", None)
        if layers is not None:
            return any(getattr(l, "constraints", None) for l in layers)
        return any(getattr(node.obj, "constraints", None)
                   for node in getattr(net, "order", ())
                   if getattr(node, "kind", None) == "layer")

    def _opt_shard_init_fn(self):
        layout = self._layout()
        optimizer = self.net._optimizer

        def init(params):
            return optimizer.init(layout.flatten(params))

        return init

    def _opt_shard_specs(self):
        """PartitionSpec tree for the sharded optimizer state: moment
        leaves (flat, padded to a multiple of n) ride ``P('data')``,
        scalar counters stay replicated."""
        from deeplearning4j_tpu.parallel.zero import sharded_leaf
        shapes = jax.eval_shape(self._opt_shard_init_fn(),
                                self.net.params)
        return jax.tree.map(
            lambda l: P("data") if sharded_leaf(l, self.n) else P(),
            shapes)

    def _init_sharded_opt(self):
        """Optimizer state born as 1/N shards: compiled with per-leaf
        ``P('data')`` out_shardings so the flat layout is materialized
        directly sharded. The wrapped net's current ``opt_state`` —
        fresh init OR a zip/trainer-restored one — is what gets
        re-sharded, so resume re-enters the exact moments the
        checkpoint held; only a net without any opt_state falls back
        to ``optimizer.init`` from scratch."""
        from deeplearning4j_tpu.parallel.zero import sharded_leaf
        mesh = self.mesh
        ref = jax.eval_shape(self._opt_shard_init_fn(),
                             self.net.params)
        out_sh = jax.tree.map(
            lambda l: NamedSharding(
                mesh, P("data") if sharded_leaf(l, self.n) else P()),
            ref)
        src = self.net.opt_state
        if src is None:
            return jax.jit(self._opt_shard_init_fn(),
                           out_shardings=out_sh)(self.net.params)
        ref_leaves = jax.tree_util.tree_leaves(ref)
        ref_def = jax.tree_util.tree_structure(ref)
        src_leaves = jax.tree_util.tree_leaves(src)
        if len(src_leaves) != len(ref_leaves):
            raise ValueError(
                "net.opt_state does not match the optimizer layout "
                f"({len(src_leaves)} leaves vs {len(ref_leaves)}) — "
                "was the updater reconfigured after restore?")

        def reshard(leaves):
            out = []
            for cur, want in zip(leaves, ref_leaves):
                cur = jnp.asarray(cur)
                if tuple(cur.shape) != tuple(want.shape):
                    cur = jnp.pad(jnp.ravel(cur),
                                  (0, int(want.shape[0]) - cur.size))
                out.append(cur.astype(want.dtype))
            return jax.tree_util.tree_unflatten(ref_def, out)

        return jax.jit(reshard, out_shardings=out_sh)(src_leaves)

    def _ensure_sharded_state(self):
        """(Re)build the 1/N optimizer shards when missing — first
        ``fit`` or after a resilience restore nulled ``_dp_state``:
        the shards come from the net's current (possibly restored)
        ``opt_state``, whose replicated copy is then evicted to host
        memory so it stops pinning N× the sharded footprint in HBM.
        The identity-tracked backref lets ``ModelSerializer``'s zip
        export fold the live shards for exactly as long as this
        wrapper owns the net's optimizer state."""
        if self._dp_state is not None:
            if self.gather_overlap and self._pshard is None:
                self._pshard = self._init_param_shards()
            return
        import weakref
        net = self.net
        self._dp_state = self._init_sharded_opt()
        net.opt_state = jax.device_get(net.opt_state)
        self._evicted_opt = net.opt_state
        net._zero_wrapper = weakref.ref(self)
        if self.gather_overlap:
            # (re)built from the net's CURRENT params — a resilience
            # restore nulls _dp_state, and the rebuild must not keep
            # pre-restore shards alive
            self._pshard = self._init_param_shards()
            self._params_stale = False

    def _param_shard_specs(self):
        """PartitionSpec tree for the overlap mode's carried param
        shards: every flat leaf is padded to a multiple of n, so every
        leaf rides ``P('data')``."""
        layout = self._layout()
        return jax.tree_util.tree_unflatten(
            layout.treedef, [P("data")] * len(layout.padded))

    def _shard_sharding_tree(self, spec):
        """Uniform ``NamedSharding`` tree over the flat-layout treedef
        (PartitionSpecs are themselves pytrees, so the spec tree can't
        be ``jax.tree.map``-ed — build from the treedef instead)."""
        layout = self._layout()
        sh = NamedSharding(self.mesh, spec)
        return jax.tree_util.tree_unflatten(
            layout.treedef, [sh] * len(layout.padded))

    def _init_param_shards(self):
        """Materialize the net's CURRENT params as flat 1/N shards —
        the carried state of the gather-overlap step (the analog of
        ``_init_sharded_opt`` for params). ``net.params`` keeps the
        replicated master view; it refreshes from the shards at fit
        exit / checkpoint time (:meth:`_materialize_params`). The
        flatten program is built ONCE per wrapper (a fresh ``jax.jit``
        per call would retrace+recompile the full-tree flatten at
        every fit entry). The leaf weakrefs record WHICH params the
        shards came from (:meth:`_params_current_in_shards` — the
        ``zoo.gpt`` ``decode_params`` staleness idiom)."""
        import weakref
        layout = self._layout()
        if self._flatten_jit is None:
            self._flatten_jit = jax.jit(
                layout.flatten,
                out_shardings=self._shard_sharding_tree(P("data")))
        self._pshard_src = [
            weakref.ref(l)
            for l in jax.tree_util.tree_leaves(self.net.params)]
        return self._flatten_jit(self.net.params)

    def _params_current_in_shards(self) -> bool:
        """Do the carried shards derive from the net's CURRENT param
        leaves? Any reassignment (loaded weights, transfer learning)
        replaces leaf arrays and breaks the ``is`` comparison, so the
        fit entry knows to re-derive; an untouched tree skips the
        rebuild (incl. the first fit right after
        ``_ensure_sharded_state`` built the shards)."""
        src = self._pshard_src
        if src is None:
            return False
        leaves = jax.tree_util.tree_leaves(self.net.params)
        return (len(src) == len(leaves)
                and all(w() is l for w, l in zip(src, leaves)))

    def _materialize_params(self):
        """Fold the carried param shards back into ``net.params``
        (overlap mode only; a no-op while params are current). The
        flat ``P('data')`` leaves ARE the full vectors globally — the
        jit just unflattens them into the natural shapes with a
        replicated layout (XLA inserts the gather); built once per
        wrapper like the flatten program."""
        import weakref
        if not self._params_stale:
            return
        layout = self._layout()
        if self._unflatten_jit is None:
            repl = NamedSharding(self.mesh, P())
            leaves_def = jax.tree_util.tree_structure(self.net.params)
            out_sh = jax.tree_util.tree_unflatten(
                leaves_def, [repl] * leaves_def.num_leaves)
            self._unflatten_jit = jax.jit(layout.unflatten,
                                          out_shardings=out_sh)
        self.net.params = self._unflatten_jit(self._pshard)
        # the materialised view derives FROM the shards: mark current
        # so the next fit entry skips a no-op re-derive
        self._pshard_src = [
            weakref.ref(l)
            for l in jax.tree_util.tree_leaves(self.net.params)]
        self._params_stale = False

    def _ensure_ready(self):
        """Step + mode state ready to train: builds on first use, and
        rebuilds mode-specific device state that a resilience restore
        dropped (``FaultTolerantTrainer._restore`` nulls ``_dp_state``
        so it is rebuilt from the RESTORED net)."""
        needs_state = (self._dp_state is None
                       and (self.mode != self.SYNC
                            or self.sharded_update))
        if self._step is None or needs_state:
            self._prepare()

    def gather_opt_state(self):
        """Materialize the sharded optimizer state in the replicated
        ``net.opt_state`` layout — export/interop only (zip
        checkpoints, updater inspection); it recreates exactly the N
        copies the sharded mode exists to avoid, so never call it in
        the training loop. Sharded checkpoints go through
        ``ShardedCheckpointer.save_wrapper`` instead."""
        if self._dp_state is None or not self.sharded_update:
            return self.net.opt_state
        ref = jax.eval_shape(self.net._optimizer.init, self.net.params)
        flat_ref = jax.tree_util.tree_leaves(ref)
        flat_cur = jax.tree_util.tree_leaves(self._dp_state)
        out = []
        for cur, want in zip(flat_cur, flat_ref):
            if tuple(cur.shape) != tuple(want.shape):
                size = int(np.prod(want.shape)) if want.shape else 1
                cur = cur[:size].reshape(want.shape)
            out.append(cur)
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(ref), out)

    # -- checkpoint glue (ShardedCheckpointer.save/restore_wrapper) ------
    def checkpoint_tree(self):
        """The wrapper's full training state as one pytree. In sharded
        mode the optimizer entry is the sharded state — each device
        saves only its 1/N (orbax/tensorstore writes shards), and a
        restore with this tree as target lands them back on the same
        topology without ever materializing the replicated layout."""
        self._ensure_ready()
        self._materialize_params()   # overlap mode: params up to date
        net = self.net
        opt = self._dp_state if self.sharded_update else net.opt_state
        return {"params": net.params, "opt": opt, "state": net.state,
                "meta": {"iteration": net.iteration,
                         "epoch": net.epoch}}

    def checkpoint_target(self):
        """Restore target for :meth:`checkpoint_tree`: abstract leaves
        carrying the mesh placement the step expects — params/state
        replicated over the mesh, optimizer moments back on their
        ``P('data')`` shards — so a restore lands every buffer where
        the compiled step will consume it."""
        tree = self.checkpoint_tree()
        repl = NamedSharding(self.mesh, P())

        def sds(leaf, sharding):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=sharding)

        return {
            "params": jax.tree.map(lambda l: sds(l, repl),
                                   tree["params"]),
            "opt": jax.tree.map(
                lambda l: sds(l, getattr(l, "sharding", repl) or repl)
                if self.sharded_update else sds(l, repl), tree["opt"]),
            "state": jax.tree.map(lambda l: sds(l, repl),
                                  tree["state"]),
            "meta": tree["meta"],
        }

    def load_checkpoint_tree(self, tree):
        """Inverse of :meth:`checkpoint_tree` (same mode/topology)."""
        self._ensure_ready()
        net = self.net
        net.params = tree["params"]
        net.state = tree["state"]
        if self.sharded_update:
            self._dp_state = tree["opt"]
            if self.gather_overlap:
                # re-scatter the restored params into the carried
                # shards the overlap step consumes
                self._pshard = self._init_param_shards()
                self._params_stale = False
        else:
            net.opt_state = tree["opt"]
        net.iteration = int(tree["meta"]["iteration"])
        net.epoch = int(tree["meta"]["epoch"])
        return self

    def load_gathered_tree(self, tree, src_layout: str = "zero-flat"):
        """Install a GATHERED checkpoint tree written at a different
        world size — the re-scatter half of resharded restore
        (``ShardedCheckpointer.restore_wrapper(reshard=True)``).

        ``tree`` holds fully-replicated leaves on this wrapper's mesh:
        params/state in their natural shapes, the optimizer state in
        the SOURCE layout (``zero-flat`` leaves padded for the source
        world size — which size is irrelevant here: re-padding is a
        pure function of the leaf and the target — or plain
        ``replicated``). Flat leaves are
        re-padded through ``zero.repad_flat_leaves`` onto THIS
        wrapper's ``FlatShardLayout`` (bit-exact on real content) and
        materialized directly as 1/N shards, exactly like
        ``_init_sharded_opt``; ``net.opt_state`` keeps a host-side
        replicated copy so zip export and later replicated fits see
        the restored moments."""
        import weakref
        from deeplearning4j_tpu.parallel.zero import (repad_flat_leaves,
                                                      sharded_leaf)
        net = self.net
        net.params = tree["params"]
        net.state = tree["state"]
        src_leaves = [np.asarray(l)
                      for l in jax.tree_util.tree_leaves(tree["opt"])]
        # replicated-layout reference: the per-leaf original shapes the
        # flat leaves unflatten back into (positionally aligned — the
        # flat and replicated optimizer trees share one treedef)
        rep_ref = jax.eval_shape(net._optimizer.init, net.params)
        rep_ref_leaves = jax.tree_util.tree_leaves(rep_ref)
        rep_def = jax.tree_util.tree_structure(rep_ref)
        if src_layout == "zero-flat":
            # route the flat→original conversion through
            # repad_flat_leaves (true-size 1-D refs, then reshape) so
            # ONE implementation owns the strict zero-tail invariant
            flat_refs = [
                want if tuple(cur.shape) == tuple(want.shape)
                else jax.ShapeDtypeStruct(
                    (int(np.prod(want.shape)) if want.shape else 1,),
                    want.dtype)
                for cur, want in zip(src_leaves, rep_ref_leaves)]
            rep_leaves = [
                np.asarray(l).reshape(tuple(want.shape))
                for l, want in zip(
                    repad_flat_leaves(src_leaves, flat_refs),
                    rep_ref_leaves)]
        else:
            rep_leaves = src_leaves
        replicated_opt = jax.tree_util.tree_unflatten(rep_def,
                                                      rep_leaves)
        if not self.sharded_update:
            repl = NamedSharding(self.mesh, P())
            net.opt_state = jax.tree.map(
                lambda l: jax.device_put(l, repl), replicated_opt)
        else:
            self._check_sharded_update_supported()
            ref = jax.eval_shape(self._opt_shard_init_fn(), net.params)
            ref_leaves = jax.tree_util.tree_leaves(ref)
            ref_def = jax.tree_util.tree_structure(ref)
            if src_layout == "zero-flat":
                flat = repad_flat_leaves(src_leaves, ref_leaves)
            else:
                flat = repad_flat_leaves(
                    [np.ravel(l) if l.ndim > 1 else l
                     for l in src_leaves], ref_leaves)
            out_sh = jax.tree.map(
                lambda l: NamedSharding(
                    self.mesh,
                    P("data") if sharded_leaf(l, self.n) else P()),
                ref)
            self._dp_state = jax.jit(
                lambda ls: jax.tree_util.tree_unflatten(ref_def, ls),
                out_shardings=out_sh)(flat)
            # host copy in the replicated layout — the same eviction
            # contract _ensure_sharded_state establishes, so
            # ModelSerializer's zip export keeps folding live shards
            net.opt_state = jax.tree.map(np.asarray, replicated_opt)
            self._evicted_opt = net.opt_state
            net._zero_wrapper = weakref.ref(self)
            if self.gather_overlap:
                self._pshard = self._init_param_shards()
                self._params_stale = False
        net.iteration = int(tree["meta"]["iteration"])
        net.epoch = int(tree["meta"]["epoch"])
        return self

    # -------------------------------------------------------------------
    def _build_sync_step(self):
        net = self.net
        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        shard = NamedSharding(mesh, P("data"))

        def step(params, opt_state, state, x, y, rng):
            loss, new_state, grads, _ = self._local_grads(
                params, state, x, y, rng)
            params, opt_state, _ = self._apply_update(params, opt_state,
                                                      grads)
            return params, opt_state, new_state, loss

        return sentry.jit(
            step, name="ParallelWrapper.sync_step",
            in_shardings=(repl, repl, repl, shard, shard, repl),
            out_shardings=(repl, repl, repl, repl),
            donate_argnums=(0, 1, 2))

    def _build_sync_sharded_step(self):
        """ZeRO-style SYNC step (arxiv 2004.13336): reduce-scatter the
        gradient mean, update this replica's 1/N flat parameter slice
        against its resident 1/N optimizer shards, all-gather the
        updated params for the next forward. Donating params lets XLA
        write the gathered result in place and start the gather before
        the host sees the step complete."""
        net = self.net
        mesh = self.mesh
        layout = self._layout()
        ospec = self._opt_shard_specs()

        def local_step(params, opt_shards, state, x, y, rng):
            loss, new_state, grads, _ = self._local_grads(
                params, state, x, y, rng)
            gshard = layout.scatter_mean(grads, "data")
            pshard = layout.shard(layout.flatten(params),
                                  jax.lax.axis_index("data"))
            pshard, opt_shards, _ = self._apply_update(
                pshard, opt_shards, gshard, constrain=False)
            params = net._apply_constraints(
                layout.gather(pshard, "data"))
            loss = jax.lax.pmean(loss, "data")
            return params, opt_shards, new_state, loss

        pspec = P()
        dspec = P("data")
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pspec, ospec, pspec, dspec, dspec, pspec),
            out_specs=(pspec, ospec, pspec, pspec),
            check_vma=False)
        return sentry.jit(smapped,
                          name="ParallelWrapper.sync_sharded_step",
                          donate_argnums=(0, 1, 2))

    def _build_sync_sharded_overlap_step(self):
        """ZeRO step with the param all-gather moved to the TOP of the
        step (arxiv 2004.13336's weight-update/communication overlap,
        the PR 5 leftover ROADMAP item 3 wanted measured): the carried
        state is the flat 1/N param shards, the step gathers them and
        runs the forward FROM the gather — each leaf's all-gather is
        independent of every layer that doesn't consume it yet, so
        XLA's latency-hiding scheduler interleaves gather traffic with
        early-layer compute instead of serializing the whole gather
        behind the update at step end. Same math as
        ``_build_sync_sharded_step`` (gather→fwd/bwd→scatter→shard
        update), reordered across the step boundary; trajectory
        equivalence is float-band like PR 5's (XLA fuses the programs
        differently)."""
        net = self.net
        mesh = self.mesh
        layout = self._layout()
        ospec = self._opt_shard_specs()
        pshard_spec = self._param_shard_specs()

        def local_step(pshard, opt_shards, state, x, y, rng):
            # gather FIRST: the forward consumes the gathered tree, so
            # every layer's gather can overlap all compute before it
            params = layout.gather(pshard, "data")
            loss, new_state, grads, _ = self._local_grads(
                params, state, x, y, rng)
            gshard = layout.scatter_mean(grads, "data")
            new_pshard, opt_shards, _ = self._apply_update(
                pshard, opt_shards, gshard, constrain=False)
            loss = jax.lax.pmean(loss, "data")
            return new_pshard, opt_shards, new_state, loss

        pspec = P()
        dspec = P("data")
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pshard_spec, ospec, pspec, dspec, dspec, pspec),
            out_specs=(pshard_spec, ospec, pspec, pspec),
            check_vma=False)
        return sentry.jit(
            smapped, name="ParallelWrapper.sync_sharded_overlap_step",
            donate_argnums=(0, 1, 2))

    def _build_sync_sharded_overlap_diag_step(self):
        """Diagnostic sibling of the overlap step: same gather-at-top
        math, plus the numerics aux outputs. The post-update params
        the diag norms/divergence fences need are NOT gathered by the
        plain overlap step — the diag variant pays one extra gather
        for them (cadence path, not the hot one)."""
        from deeplearning4j_tpu.obs import numerics
        net = self.net
        mesh = self.mesh
        layout = self._layout()
        ospec = self._opt_shard_specs()
        pshard_spec = self._param_shard_specs()
        nm = net._numerics
        histograms = nm.histograms if nm is not None else False
        layers = net._layer_names()

        def local_step(pshard, opt_shards, state, x, y, rng):
            params = layout.gather(pshard, "data")
            loss, new_state, grads, act_stats = self._local_grads(
                params, state, x, y, rng, want_stats=True)
            local_norms = numerics.layer_norms_vector(grads, layers)
            divergence = (jax.lax.pmax(local_norms, "data")
                          - jax.lax.pmin(local_norms, "data"))
            gshard = layout.scatter_mean(grads, "data")
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "data"), grads)
            act_stats = numerics.reduce_act_stats(act_stats, "data")
            new_pshard, opt_shards, ushard = self._apply_update(
                pshard, opt_shards, gshard, constrain=False)
            new_params = layout.gather(new_pshard, "data")
            updates = layout.gather(ushard, "data")
            diag = numerics.build_diag(new_params, grads, updates,
                                       act_stats, layers,
                                       histograms=histograms)
            diag["replica_divergence"] = divergence
            pnorms = numerics.layer_norms_vector(new_params, layers)
            diag["param_replica_divergence"] = (
                jax.lax.pmax(pnorms, "data")
                - jax.lax.pmin(pnorms, "data"))
            loss = jax.lax.pmean(loss, "data")
            return (new_pshard, opt_shards, new_state, loss,
                    numerics.pack_diag(diag))

        pspec = P()
        dspec = P("data")
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pshard_spec, ospec, pspec, dspec, dspec, pspec),
            out_specs=(pshard_spec, ospec, pspec, pspec, pspec),
            check_vma=False)
        return sentry.jit(
            smapped,
            name="ParallelWrapper.sync_sharded_overlap_diag_step",
            donate_argnums=(0, 1, 2))

    def _build_sync_diag_step(self):
        """Diagnostic variant of the SYNC step (obs/numerics.py,
        ARCHITECTURE.md §11): an explicit ``shard_map`` computes each
        replica's local gradients, reduces them with ``pmean`` (the
        same mean the plain step's XLA-inserted allreduce produces on
        equal shards), and emits the numerics aux outputs — including
        per-layer replica divergence, the ``pmax − pmin`` spread of
        the per-replica gradient norms that the fused global-gradient
        program cannot see."""
        from deeplearning4j_tpu.obs import numerics
        net = self.net
        mesh = self.mesh
        nm = net._numerics
        histograms = nm.histograms if nm is not None else False
        layers = net._layer_names()

        def local_step(params, opt_state, state, x, y, rng):
            loss, new_state, grads, act_stats = self._local_grads(
                params, state, x, y, rng, want_stats=True)
            # per-replica grad-norm spread BEFORE the mean erases it
            local_norms = numerics.layer_norms_vector(grads, layers)
            divergence = (jax.lax.pmax(local_norms, "data")
                          - jax.lax.pmin(local_norms, "data"))
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "data"), grads)
            act_stats = numerics.reduce_act_stats(act_stats, "data")
            params, opt_state, updates = self._apply_update(
                params, opt_state, grads)
            diag = numerics.build_diag(params, grads, updates,
                                       act_stats, layers,
                                       histograms=histograms)
            diag["replica_divergence"] = divergence
            loss = jax.lax.pmean(loss, "data")
            return (params, opt_state, new_state, loss,
                    numerics.pack_diag(diag))

        pspec = P()          # replicated params/state/diag
        dspec = P("data")    # sharded batch
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pspec, pspec, pspec, dspec, dspec, pspec),
            out_specs=(pspec, pspec, pspec, pspec, pspec),
            check_vma=False)
        return sentry.jit(smapped, name="ParallelWrapper.sync_diag_step",
                          donate_argnums=(0, 1, 2))

    def _build_sync_sharded_diag_step(self):
        """Diagnostic variant of the SHARDED SYNC step: the exact
        scatter→shard-update→gather math of the plain sharded step
        (so diag iterations stay on the training trajectory), plus the
        numerics aux outputs. Emits BOTH divergence fences: the PR 4
        per-replica grad-norm spread (nonzero by design — replicas see
        different shards) and ``param_replica_divergence``, the spread
        of per-replica norms of the POST-GATHER params — the ZeRO
        lockstep invariant, exactly 0.0 while replicas agree
        bit-for-bit."""
        from deeplearning4j_tpu.obs import numerics
        net = self.net
        mesh = self.mesh
        layout = self._layout()
        ospec = self._opt_shard_specs()
        nm = net._numerics
        histograms = nm.histograms if nm is not None else False
        layers = net._layer_names()

        def local_step(params, opt_shards, state, x, y, rng):
            loss, new_state, grads, act_stats = self._local_grads(
                params, state, x, y, rng, want_stats=True)
            local_norms = numerics.layer_norms_vector(grads, layers)
            divergence = (jax.lax.pmax(local_norms, "data")
                          - jax.lax.pmin(local_norms, "data"))
            gshard = layout.scatter_mean(grads, "data")
            # full mean grads are diag-only outputs (per-layer norms);
            # the update itself consumes only the scattered shards
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, "data"), grads)
            act_stats = numerics.reduce_act_stats(act_stats, "data")
            pshard = layout.shard(layout.flatten(params),
                                  jax.lax.axis_index("data"))
            pshard, opt_shards, ushard = self._apply_update(
                pshard, opt_shards, gshard, constrain=False)
            params = net._apply_constraints(
                layout.gather(pshard, "data"))
            updates = layout.gather(ushard, "data")
            diag = numerics.build_diag(params, grads, updates,
                                       act_stats, layers,
                                       histograms=histograms)
            diag["replica_divergence"] = divergence
            pnorms = numerics.layer_norms_vector(params, layers)
            diag["param_replica_divergence"] = (
                jax.lax.pmax(pnorms, "data")
                - jax.lax.pmin(pnorms, "data"))
            loss = jax.lax.pmean(loss, "data")
            return (params, opt_shards, new_state, loss,
                    numerics.pack_diag(diag))

        pspec = P()
        dspec = P("data")
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pspec, ospec, pspec, dspec, dspec, pspec),
            out_specs=(pspec, ospec, pspec, pspec, pspec),
            check_vma=False)
        return sentry.jit(
            smapped, name="ParallelWrapper.sync_sharded_diag_step",
            donate_argnums=(0, 1, 2))

    def _build_encoded_step(self):
        mesh = self.mesh
        acc = self.accumulator

        def local_step(params, opt_state, state, acc_state, x, y, rng):
            # strip per-device leading axis from the residual state
            acc_state = _replica_view(acc_state)
            # per-device grads on the local shard
            loss, new_state, grads, _ = self._local_grads(
                params, state, x, y, rng)
            grads, acc_state = acc.exchange(grads, acc_state, "data")
            params, opt_state, _ = self._apply_update(params, opt_state,
                                                      grads)
            loss = jax.lax.pmean(loss, "data")
            return (params, opt_state, new_state, _stacked(acc_state),
                    loss)

        pspec = P()          # replicated params
        dspec = P("data")    # sharded batch / per-device residuals
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pspec, pspec, pspec, dspec, dspec, dspec, pspec),
            out_specs=(pspec, pspec, pspec, dspec, pspec),
            check_vma=False)
        return sentry.jit(smapped, name="ParallelWrapper.encoded_step",
                          donate_argnums=(0, 1, 2, 3))

    def _build_async_step(self):
        mesh = self.mesh
        acc = self.accumulator

        def local_step(params, opt_state, state, acc_state, x, y, rng):
            # per-replica params/opt + per-replica residual/inflight
            params = _replica_view(params)
            opt_state = _replica_view(opt_state)
            acc_state = _replica_view(acc_state)
            loss, new_state, grads, _ = self._local_grads(
                params, state, x, y, rng)
            grads, acc_state = acc.exchange_async(grads, acc_state,
                                                  "data")
            params, opt_state, _ = self._apply_update(params, opt_state,
                                                      grads)
            loss = jax.lax.pmean(loss, "data")
            return (_stacked(params), _stacked(opt_state), new_state,
                    _stacked(acc_state), loss)

        pdev = P("data")
        repl = P()
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pdev, pdev, repl, pdev, pdev, pdev, repl),
            out_specs=(pdev, pdev, repl, pdev, repl),
            check_vma=False)
        return sentry.jit(smapped, name="ParallelWrapper.async_step",
                          donate_argnums=(0, 1, 2, 3))

    def _build_averaging_step(self):
        mesh = self.mesh
        k = self.averaging_frequency
        avg_upd = self.average_updaters

        def pmean_floats(tree):
            # optimizer state holds non-float leaves too (step counts);
            # those are replica-identical — average only the moments
            return jax.tree.map(
                lambda a: jax.lax.pmean(a, "data")
                if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)

        def local_step(params, opt_state, state, x, y, rng, it):
            params = _replica_view(params)
            opt_state = _replica_view(opt_state)
            loss, new_state, grads, _ = self._local_grads(
                params, state, x, y, rng)
            params, opt_state, _ = self._apply_update(params, opt_state,
                                                      grads)
            # every k-th iteration: replica averaging (reference
            # ParameterAveraging semantics; averageUpdaters=true also
            # averages the optimizer moments)
            do_avg = (it % k) == (k - 1)
            params, opt_state = jax.lax.cond(
                do_avg,
                lambda po: (pmean_floats(po[0]),
                            pmean_floats(po[1]) if avg_upd else po[1]),
                lambda po: po, (params, opt_state))
            loss = jax.lax.pmean(loss, "data")
            return (_stacked(params), _stacked(opt_state), new_state,
                    loss)

        pdev = P("data")   # leading device axis
        repl = P()
        smapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(pdev, pdev, repl, pdev, pdev, repl, repl),
            out_specs=(pdev, pdev, repl, repl),
            check_vma=False)
        return sentry.jit(smapped, name="ParallelWrapper.averaging_step",
                          donate_argnums=(0, 1, 2))

    # -------------------------------------------------------------------
    def _prepare(self):
        net = self.net
        if self.mode == self.SYNC:
            if self.sharded_update:
                self._check_sharded_update_supported()
                if self.gather_overlap:
                    self._step = self._build_sync_sharded_overlap_step()
                    self._step_builder = \
                        "_build_sync_sharded_overlap_step"
                else:
                    self._step = self._build_sync_sharded_step()
                    self._step_builder = "_build_sync_sharded_step"
                self._ensure_sharded_state()
            else:
                self._step = self._build_sync_step()
                self._step_builder = "_build_sync_step"
        elif self.mode == self.ENCODED:
            self._step = self._build_encoded_step()
            self._step_builder = "_build_encoded_step"
            if self._dp_state is None:
                # per-device residual state: leading axis over devices
                one = self.accumulator.init_state(net.params)
                self._dp_state = {
                    "residual": jax.tree.map(
                        lambda a: jnp.broadcast_to(
                            a[None], (self.n,) + a.shape),
                        one["residual"]),
                    "tau": jnp.broadcast_to(one["tau"][None], (self.n,)),
                }
        elif self.mode == self.AVERAGING:
            self._step = self._build_averaging_step()
            self._step_builder = "_build_averaging_step"
            if self._dp_state is None:
                self._dp_state = (
                    jax.tree.map(lambda a: jnp.broadcast_to(
                        a[None], (self.n,) + a.shape), net.params),
                    jax.tree.map(lambda a: jnp.broadcast_to(
                        a[None], (self.n,) + a.shape), net.opt_state),
                )
        elif self.mode == self.ASYNC:
            self._step = self._build_async_step()
            self._step_builder = "_build_async_step"
            if self._dp_state is None:
                stack = lambda a: jnp.broadcast_to(
                    a[None], (self.n,) + a.shape)
                self._dp_state = (
                    jax.tree.map(stack, net.params),
                    jax.tree.map(stack, net.opt_state),
                    jax.tree.map(stack,
                                 self.accumulator.init_async_state(
                                     net.params)),
                )
        else:
            raise ValueError(f"unknown mode {self.mode!r}")
        self._export_opt_state_bytes()

    def _export_opt_state_bytes(self):
        """Publish the per-device optimizer-state footprint of the
        active layout (the headline HBM number sharded_update moves)."""
        if self.mode == self.SYNC and self.sharded_update:
            layout, nbytes = "sharded", per_device_bytes(
                self._dp_state, self.n)
        elif self.mode in (self.AVERAGING, self.ASYNC):
            # per-replica stacks: each device holds one full copy
            layout, nbytes = "replicated", per_device_bytes(
                self._dp_state[1], self.n)
        else:
            layout, nbytes = "replicated", per_device_bytes(
                self.net.opt_state)
        obs.metrics.OPT_STATE_BYTES.labels(layout=layout).set(nbytes)

    def _diag_builder_name(self):
        if self.sharded_update and self.gather_overlap:
            return "_build_sync_sharded_overlap_diag_step"
        return ("_build_sync_sharded_diag_step" if self.sharded_update
                else "_build_sync_diag_step")

    def _ensure_diag_step(self, nm):
        """(Re)build the SYNC diagnostic step for the attached
        monitor: the monitor's config (histogram sketches on/off) is
        traced into the program."""
        if self._diag_step is None or self._diag_step_monitor is not nm:
            self._diag_step = getattr(self, self._diag_builder_name())()
            self._diag_step_monitor = nm
        return self._diag_step

    def warmup(self, specs):
        """AOT-compile the SPMD train step (and, with a numerics
        monitor attached, its diagnostic sibling) for every declared
        batch shape before the first real batch (see ``perf.warmup``):
        the first step of a fresh worker process otherwise stalls the
        whole mesh on its compile. Spec features/labels carry the
        GLOBAL batch dim (what ``fit`` feeds the step after trimming).

        Feeds come from the module-level ``WARMUP_FEEDS`` table — one
        entry per step builder, enforced by
        ``tools/lint_instrumentation.py`` rule 4 so a new step variant
        cannot ship without a warmup path."""
        from deeplearning4j_tpu.perf.warmup import (_feature_sds,
                                                    _label_sds,
                                                    sharded_sds)
        net = self.net
        self._ensure_ready()
        # fit feeds batch-sharded global arrays (_stage lays every
        # batch over the mesh through make_global_batch, on one host
        # as on several), and jit's dispatch cache keys on input
        # sharding — lower from the SAME sharding or the first real
        # step recompiles invisibly (sentry signatures ignore sharding
        # by design)
        dshard = NamedSharding(self.mesh, P("data"))
        rng = jax.random.fold_in(jax.random.PRNGKey(net.conf.seed), 0)
        entries = [(self._step, self._step_builder)]
        nm = getattr(net, "_numerics", None)
        if nm is not None and self.mode == self.SYNC:
            # the cadence-gated diagnostic step is a second compiled
            # program over the same signature — warm it too or the
            # first diagnostic iteration stalls on its compile
            entries.append((self._ensure_diag_step(nm),
                            self._diag_builder_name()))
        compiled, seconds = 0, 0.0
        for spec in specs:
            if not spec.train:
                continue
            x = sharded_sds(_feature_sds(spec, net.conf), dshard)
            y = sharded_sds(_label_sds(spec, net.conf), dshard)
            for step, builder in entries:
                dt = step.warmup(*WARMUP_FEEDS[builder](self, x, y, rng))
                compiled += dt > 0
                seconds += dt
        return {"compiled": compiled, "seconds": seconds}

    def _pull(self, src):
        """The iterator's next batch, or ``None`` at its end; the wait
        is the loop's ETL time."""
        te0 = obs.now()
        try:
            ds = next(src)
        except StopIteration:
            return None
        obs.record_etl("ParallelWrapper.fit", te0, obs.now())
        return ds

    def _stage(self, ds, b_local):
        """Trim a batch to what the mesh divides and enqueue its copy
        from host memory onto the chips that will read it: every leaf
        goes over the mesh under the sharding each step builder
        declares for its batch arguments (``P("data")``), a 1/N slice
        a chip — no copy of the whole batch on the default device, no
        chip-to-chip re-lay in front of the step; multi-host, each
        process feeds its local shard of ONE global array. The call
        returns when the copies are enqueued, not when they land.
        Returns ``(x, y, host bytes enqueued)``; ``x`` is ``None`` for
        a batch smaller than the worker count, which ``fit`` drops."""
        from deeplearning4j_tpu.parallel.master import make_global_batch
        x, y = ds.features, ds.labels
        bsz = jax.tree.leaves(x)[0].shape[0]
        b = bsz - (bsz % self.n) if b_local is None else b_local
        if bsz < b:
            raise ValueError(
                f"batch of {bsz} smaller than the "
                f"agreed per-process size {b}: multi-host "
                "training needs uniform batches (drop or pad "
                "the ragged remainder)")
        if b == 0:
            import logging
            logging.getLogger("deeplearning4j_tpu").warning(
                "ParallelWrapper: dropping batch of %d examples "
                "(< %d workers); use batch sizes divisible by "
                "the worker count", bsz, self.n)
            return None, None, 0
        trim = lambda a: a[:b]
        x, y = jax.tree.map(trim, x), jax.tree.map(trim, y)
        nbytes = sum(a.nbytes for a in jax.tree.leaves((x, y)))
        return (*make_global_batch(self.mesh, x, y), nbytes)

    def _guarded(self, fn):
        """Run a step dispatch under the elastic collective watchdog
        when a context is installed (the collective may block INSIDE
        the dispatch, not only at the loss sync — e.g. gloo CPU runs
        the program synchronously); plain call otherwise."""
        if self.elastic is None:
            return fn()
        return self.elastic.run(fn)

    def _book(self, diag=None):
        """A step's bookkeeping once its loss is in ``net.score_``:
        the iteration count, the numerics monitor, the listeners."""
        net = self.net
        net.iteration += 1
        nm = getattr(net, "_numerics", None)
        if diag is not None:
            # publishes per-layer gauges incl. the replica-
            # divergence family; raises NonFiniteError with
            # cross-replica attribution when the sentinel fired
            nm.process(net, diag, net._layer_names(),
                       entry="ParallelWrapper")
        elif nm is not None:
            nm.note_score(net.score_)
        for l in net.listeners:
            l.iteration_done(net, net.iteration, net.epoch)

    def _state_read_at(self, iteration) -> bool:
        """Whether a listener says its ``iteration_done`` at
        ``iteration`` reads the net's state (``reads_state``: a
        checkpoint, an evaluation, the trainer's progress file)."""
        for l in self.net.listeners:
            reads = getattr(l, "reads_state", None)
            if reads is not None and reads(iteration):
                return True
        return False

    def _drain(self):
        """Read and book the step in flight, if there is one, outside
        a launch: at an epoch's end, before a diagnostic step, before
        the step after one whose listeners read the net's state, and
        when the host's side raises with a step on the chips."""
        loss, self._flight = self._flight, None
        if loss is None:
            return
        t0 = obs.now()
        self.net.score_ = float(loss)
        obs.record_worker_drain(f"proc{jax.process_index()}", t0,
                                obs.now())
        self._book()

    def fit(self, iterator, epochs: int = 1):
        """Reference: ParallelWrapper.fit(DataSetIterator).

        The loop keeps one BATCH and one STEP in flight. Each batch
        goes from host memory straight onto the chips that will read
        it, laid out as the step declares its batch arguments
        (``_stage``); with step n on the chips and batch n+1 staged
        beside it, the loop launches step n+1 on step n's device
        outputs, pulls batch n+2 and enqueues its copy, and only THEN
        reads step n's loss, advances ``net.iteration`` and calls step
        n's listeners. The chips go from one step into the next; the
        launch, the read-back, the bookkeeping and the listeners run
        under a step. Only a call's (and an epoch's) first step finds
        the pipeline empty, and ``fit`` returns when the last step's
        loss has been read and its listeners called: ``net.params``,
        ``net.opt_state``, ``net.score_`` and ``net.iteration`` are
        final at return. In every mode; ``prefetch_buffer`` still
        counts HOST batches.

        What a listener may assume: every step's loss is read, in
        order, before THAT step's bookkeeping and listeners, so the
        sequence of ``(iteration, epoch, net.score_)`` it sees is the
        blocking loop's. A listener that SAVES or EVALUATES the net
        says so (``TrainingListener.reads_state(iteration)``, as
        ``CheckpointListener``, ``EvaluativeListener`` and
        ``FaultTolerantTrainer``'s progress tracker do at their
        cadence): at such an iteration it finds ``net.params``,
        ``net.opt_state`` and ``net.state`` as that very step left
        them, so a periodic checkpoint resumes batch for batch. What a
        listener that does not say so may not assume: ``net.params``
        read inside ``iteration_done`` may be one step NEWER than
        ``iteration`` — the step launched ahead has taken them over,
        as ``fit(steps_per_loop=k)``'s listeners find the whole group
        applied. A non-finite loss escalates the numerics monitor one
        step later than it did.

        When something raises: a step's ``rng`` and iteration come from
        the number the step WILL have, so an interrupted run folds what
        the blocking loop folds. An error of the host's side (the
        iterator's or the trim's, held as before until the step is
        booked; the ``worker_step`` site; a listener; a ``Preempted``)
        finds a sound step on the chips whose update ``net.params``
        already holds: that step is read, booked and shown to the
        listeners, THEN the error is raised, so params, iteration and
        the listeners' view agree on every exit (a preemption's
        checkpoint resumes bit for bit). So a LISTENER's error at step
        n books step n+1 too and calls the listeners once more, the
        one that raised among them; what that drain raises in its turn
        (the read, a listener again) is noted on the first error and
        not raised in its place. A step whose READ raises (a device
        fault) takes the step launched on its outputs with it: dropped
        unread, no record, no bookkeeping, no listener,
        ``net.iteration`` stands; so does an interrupt
        (``KeyboardInterrupt``, ``SystemExit``), which waits for no
        step and runs no listener. Either way the batch staged ahead
        is dropped; the iterator is then at most one batch further
        than the last step launched.

        The loop reads a step before it launches the next, the order
        it had, where it can see that the step's result is needed
        first: under an elastic context (``self.elastic``: the
        ``pre_step``/``post_step`` stamps, the watchdog's sync, the
        barrier a dead peer must break), for a diagnostic step that is
        due (the numerics monitor's cadence: the step in flight is
        drained, the diagnostic step runs alone) and after a step at
        which a listener ``reads_state`` (that step is read and its
        listeners called before the next is launched). Told from
        those three alone; there is no switch.

        Multi-host (jax.process_count() > 1): every jitted step is a
        collective spanning all hosts, so the processes must agree on
        the number and shape of steps. The iterator (or its wrapped
        base) must be sized (``__len__``); the per-epoch step count is
        the cross-process minimum, each local batch is trimmed to the
        cross-process minimum batch size, and a batch smaller than that
        raises instead of desyncing the cluster.
        """
        try:
            return self._fit_epochs(iterator, epochs)
        except (Exception, Preempted) as e:
            # the host's side raised with a sound step on the chips
            # whose update net.params already holds: book it, then
            # raise. The error that stopped the loop is the one the
            # caller's retry policy must see, so what the drain raises
            # (the read after a host error, a listener once more: the
            # trainer's tracker repeats its Preempted) rides on it
            try:
                self._drain()
            except (Exception, Preempted) as drained:
                e.add_note("draining the step in flight raised "
                           f"{drained!r}")
            raise
        finally:
            # an interrupt (KeyboardInterrupt, SystemExit) waits for no
            # step and runs no listener: the flight is dropped unread
            self._flight = None
            # gather-overlap: net.params must not be left stale on ANY
            # exit — including NonFiniteError/preemption unwinds (the
            # carried shards are the live truth a post-mortem reads).
            # Best-effort: a step that died mid-donation can leave
            # unusable shard buffers; the original exception must
            # still propagate over a failed materialize.
            if self._params_stale:
                try:
                    self._materialize_params()
                except Exception:
                    import logging
                    logging.getLogger("deeplearning4j_tpu").warning(
                        "gather_overlap: could not materialize "
                        "net.params after an interrupted fit — the "
                        "live weights remain in the carried shards")

    def _fit_epochs(self, iterator, epochs: int):
        net = self.net
        self._ensure_ready()
        if (self.gather_overlap and self._pshard is not None
                and not self._params_stale
                and not self._params_current_in_shards()):
            # the user assigned net.params between fits (loaded
            # weights, transfer learning): re-derive the carried
            # shards so the overlap step trains FROM them. Leaf
            # identity tracking skips the rebuild when the tree is
            # untouched (first fit, or a fit right after the exit
            # materialise).
            self._pshard = self._init_param_shards()
        from deeplearning4j_tpu.data.iterators import AsyncDataSetIterator
        multi = jax.process_count() > 1
        # divisibility is a LOCAL constraint: this process's batch
        # splits over its local devices; equal trims keep the global
        # batch divisible by the full mesh
        local_n = max(1, self.n // jax.process_count())
        n_steps = None          # per-epoch step budget (multi-host)
        b_local = None          # agreed per-process batch size
        if multi:
            from jax.experimental import multihost_utils as mhu
            try:
                n_local = len(iterator)
            except TypeError:
                raise ValueError(
                    "multi-host ParallelWrapper.fit needs a sized "
                    "iterator (len()) so all processes can agree on "
                    "the step count") from None
            counts = np.asarray(mhu.process_allgather(
                jnp.asarray([n_local], jnp.int32)))
            n_steps = int(counts.min())
            first = next(iter(iterator))
            first_b = jax.tree.leaves(first.features)[0].shape[0]
            b0 = first_b - (first_b % local_n)
            sizes = np.asarray(mhu.process_allgather(
                jnp.asarray([b0], jnp.int32)))
            b_local = int(sizes.min())
            if b_local == 0:
                raise ValueError(
                    f"per-process batch ({first_b}) "
                    f"smaller than local device count ({local_n})")
        it = AsyncDataSetIterator(iterator, self.prefetch_buffer) \
            if self.prefetch_buffer else iterator
        # worker identity for telemetry: one fit loop per process; the
        # heartbeat gauge + stale detector key on it (obs/health.py)
        worker = f"proc{jax.process_index()}"
        for _ in range(epochs):
            if hasattr(it, "reset"):
                it.reset()
            step_i = 0
            src = iter(it)
            # (batch, its staged arrays): the iterator's next batch,
            # enqueued onto the chips while the step before it ran;
            # one deep, and dropped with the frame when a step raises.
            # The STEP in flight is self._flight: fit outlives the
            # frame to read it when the host's side raises
            ahead = None
            while True:
                if ahead is None:
                    # nothing was staged ahead (the call's first
                    # batch, or the lockstep budget was spent): this
                    # iteration stages its own
                    ahead = (self._pull(src), None)
                (ds, staged), ahead = ahead, None
                if ds is None:
                    break
                faults.inject("worker_step")  # site: worker loop body
                if n_steps is not None and step_i >= n_steps:
                    break               # stay in lockstep across hosts
                # the iteration number this step WILL have: the step in
                # flight takes net.iteration when its loss is read
                step_it = net.iteration + (self._flight is not None)
                nm = getattr(net, "_numerics", None)
                diag_due = nm is not None and nm.due(step_it)
                run_diag = diag_due and self.mode == self.SYNC
                if run_diag or (self._flight is not None and
                                self._state_read_at(net.iteration + 1)):
                    # a diagnostic step's numbers are processed against
                    # the state it left: nothing unread behind it,
                    # nothing launched ahead of it. So is what a
                    # listener saves or evaluates: where one says the
                    # step in flight is such a step (reads_state), its
                    # listeners run before the next step takes
                    # net.params over
                    self._drain()
                if self.elastic is not None:
                    # mesh-epoch stamp + lease renewal + the
                    # host_death drill site (resilience/elastic.py) —
                    # AFTER the lockstep break, so a surplus local
                    # batch never stamps a phantom barrier entry for
                    # a step the fleet will never dispatch
                    self.elastic.pre_step(net.iteration)
                t0 = obs.now()
                staged_ahead = staged is not None
                if not staged_ahead:
                    staged = self._stage(ds, b_local)
                x, y, nbytes = staged
                if x is None:
                    continue            # smaller than the mesh: dropped
                if staged_ahead:
                    nbytes = 0      # enqueued by the iteration before
                step_i += 1
                rng = jax.random.fold_in(
                    jax.random.PRNGKey(net.conf.seed), step_it)
                t1 = obs.now()
                # from the launch until the next batch is enqueued the
                # host must not stop for a full collection (_gc_held)
                with _gc_held():
                    diag = None
                    if diag_due and not run_diag and \
                            not self._diag_unsupported_warned:
                        self._diag_unsupported_warned = True
                        import logging
                        logging.getLogger("deeplearning4j_tpu").warning(
                            "numerics observatory: diagnostic steps are "
                            "implemented for SYNC mode only; %r trains "
                            "without in-step diagnostics", self.mode)
                    if run_diag:
                        self._ensure_diag_step(nm)
                        if self.sharded_update and self.gather_overlap:
                            (self._pshard, self._dp_state, net.state, loss,
                             diag) = self._guarded(
                                lambda: self._diag_step(
                                    self._pshard, self._dp_state,
                                    net.state, x, y, rng))
                            self._params_stale = True
                        elif self.sharded_update:
                            (net.params, self._dp_state, net.state, loss,
                             diag) = self._guarded(
                                lambda: self._diag_step(
                                    net.params, self._dp_state, net.state,
                                    x, y, rng))
                        else:
                            (net.params, net.opt_state, net.state, loss,
                             diag) = self._guarded(
                                lambda: self._diag_step(
                                    net.params, net.opt_state, net.state,
                                    x, y, rng))
                    elif self.mode == self.SYNC:
                        if self.sharded_update and self.gather_overlap:
                            (self._pshard, self._dp_state, net.state,
                             loss) = self._guarded(
                                lambda: self._step(
                                    self._pshard, self._dp_state,
                                    net.state, x, y, rng))
                            self._params_stale = True
                        elif self.sharded_update:
                            (net.params, self._dp_state, net.state,
                             loss) = self._guarded(
                                lambda: self._step(
                                    net.params, self._dp_state, net.state,
                                    x, y, rng))
                        else:
                            net.params, net.opt_state, net.state, loss = \
                                self._guarded(
                                    lambda: self._step(
                                        net.params, net.opt_state,
                                        net.state, x, y, rng))
                    elif self.mode == self.ENCODED:
                        (net.params, net.opt_state, net.state,
                         self._dp_state, loss) = self._guarded(
                            lambda: self._step(
                                net.params, net.opt_state, net.state,
                                self._dp_state, x, y, rng))
                    elif self.mode == self.ASYNC:
                        p, o, a = self._dp_state
                        p, o, net.state, a, loss = self._guarded(
                            lambda: self._step(p, o, net.state, a, x, y,
                                               rng))
                        self._dp_state = (p, o, a)
                    else:  # AVERAGING
                        p, o = self._dp_state
                        p, o, net.state, loss = self._guarded(
                            lambda: self._step(
                                p, o, net.state, x, y, rng,
                                jnp.asarray(step_it, jnp.int32)))
                        self._dp_state = (p, o)
                    # the step runs on its predecessor's device
                    # outputs, whose loss is read below, under it. An
                    # elastic context (stamps, the watchdog's sync, the
                    # barrier a dead peer must break) and a diagnostic
                    # step need each result before the next step may
                    # start: they read this step's own
                    launched_ahead = self._flight is not None
                    if self.elastic is not None or run_diag:
                        reads = loss
                    else:
                        reads, self._flight = self._flight, loss
                    ahead_s, ahead_error = 0.0, None
                    if n_steps is None or step_i < n_steps:
                        # the step is on the chips and nothing waits for
                        # it yet: pull the next batch and enqueue its copy
                        # now, so that it crosses while the chips compute
                        # and the next step finds it where it reads it
                        try:
                            nxt = self._pull(src)
                            ta = obs.now()
                            staged = None if nxt is None \
                                else self._stage(nxt, b_local)
                            ahead_s = obs.now() - ta
                        except Exception as e:
                            # the iterator's (or the trim's) error belongs
                            # after the loss, bookkeeping and listeners of
                            # every step launched (fit drains the last)
                            ahead_error = e
                        else:
                            ahead = (nxt, staged)
                            if staged is not None:
                                nbytes += staged[2]
                t3 = t2 = obs.now()
                if reads is not None:
                    # the float() blocks on the step it reads AND its
                    # averaging / all-reduce collective — this wait is
                    # the visible collective-sync wall time: of the
                    # step launched the iteration before, while the
                    # one just launched waits behind it on the chips.
                    # Under an elastic context it is this step's own
                    # and runs on the watchdog, so a dead peer raises
                    # within the lease window instead of hanging forever
                    try:
                        net.score_ = float(reads) if self.elastic is None \
                            else self.elastic.sync(reads)
                    except BaseException:
                        # the step launched on a failed step's outputs
                        # goes with it, unread: no record, no
                        # bookkeeping, no listener
                        self._flight = None
                        raise
                    # stamp the step end BEFORE the fleet hook: the
                    # cadence-gated snapshot publish fsyncs to the
                    # shared dir, and that I/O must not masquerade as
                    # collective-sync wall time in the very metrics
                    # the straggler hunt reads
                    t3 = obs.now()
                if self.elastic is not None:
                    # fleet plane: barrier-exit stamp + flight-recorder
                    # ring + cadence-gated telemetry publish (a no-op
                    # branch when no FleetTelemetry is installed)
                    self.elastic.post_step(net.iteration, net.score_)
                # the record of the step LAUNCHED, each phase what
                # the thread did in this iteration. h2d: the enqueueing,
                # whichever batch it was for (ahead_s of it after the
                # dispatch, drawn in front of it); dispatch: this
                # step's launch; collective_sync: the blocking read
                # above, zero long where the pipeline was empty
                obs.record_worker_step(worker, t0, t1 + ahead_s, t2, t3,
                                       nbytes, staged_ahead, launched_ahead)
                if reads is not None:
                    self._book(diag)
                if ahead_error is not None:
                    raise ahead_error
            # the epoch's last step: its listeners see the epoch it ran in
            self._drain()
            net.epoch += 1
        # normal completion: retire the liveness beat so a lingering
        # process doesn't read as a stale worker forever (a crashed
        # loop skips this and the alarm fires, as it should)
        obs.health.retire(worker)
        if self.mode in (self.AVERAGING, self.ASYNC):
            self._sync_back()
        # (gather-overlap materialize happens in fit()'s finally, so
        # exception exits refresh net.params too)
        return net

    def _sync_back(self):
        """After averaging/async-mode training, fold replicas back into
        the wrapped net (reference: ParallelWrapper final params
        copy; averageUpdaters also folds the optimizer moments as the
        replica mean rather than replica 0's)."""
        p, o = self._dp_state[0], self._dp_state[1]
        self.net.params = jax.tree.map(lambda a: jnp.mean(a, axis=0), p)
        if self.mode == self.AVERAGING and self.average_updaters:
            self.net.opt_state = jax.tree.map(
                lambda a: jnp.mean(a, axis=0)
                if jnp.issubdtype(a.dtype, jnp.floating) else a[0], o)
        else:
            self.net.opt_state = jax.tree.map(lambda a: a[0], o)


#: warmup feed per step builder: (wrapper, x, y, rng) -> the exact
#: argument tuple ``fit`` will pass the compiled step. ``warmup()``
#: iterates this table, and ``tools/lint_instrumentation.py`` rule 4
#: asserts its keys cover every ``_build_*_step`` method on
#: ParallelWrapper — a new step variant without a feed here fails
#: tier-1 instead of silently cold-tracing on its first real batch.
WARMUP_FEEDS = {
    "_build_sync_step": lambda w, x, y, rng: (
        w.net.params, w.net.opt_state, w.net.state, x, y, rng),
    "_build_sync_diag_step": lambda w, x, y, rng: (
        w.net.params, w.net.opt_state, w.net.state, x, y, rng),
    "_build_sync_sharded_step": lambda w, x, y, rng: (
        w.net.params, w._dp_state, w.net.state, x, y, rng),
    "_build_sync_sharded_diag_step": lambda w, x, y, rng: (
        w.net.params, w._dp_state, w.net.state, x, y, rng),
    "_build_sync_sharded_overlap_step": lambda w, x, y, rng: (
        w._pshard, w._dp_state, w.net.state, x, y, rng),
    "_build_sync_sharded_overlap_diag_step": lambda w, x, y, rng: (
        w._pshard, w._dp_state, w.net.state, x, y, rng),
    "_build_encoded_step": lambda w, x, y, rng: (
        w.net.params, w.net.opt_state, w.net.state, w._dp_state, x, y,
        rng),
    "_build_async_step": lambda w, x, y, rng: (
        w._dp_state[0], w._dp_state[1], w.net.state, w._dp_state[2],
        x, y, rng),
    "_build_averaging_step": lambda w, x, y, rng: (
        w._dp_state[0], w._dp_state[1], w.net.state, x, y, rng,
        jnp.asarray(0, jnp.int32)),
}

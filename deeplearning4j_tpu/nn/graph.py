"""ComputationGraph — DAG models.

Reference: ``org.deeplearning4j.nn.graph.ComputationGraph`` +
``ComputationGraphConfiguration.GraphBuilder`` (SURVEY §2.3):
multi-input/multi-output networks of layers and vertices.

TPU-native: the DAG is walked once at trace time (plain Python in
topological order) — XLA sees a single fused computation; there is no
per-vertex dispatch at runtime. One jitted train step covers all
outputs and losses.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn import _fit_ahead
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.nn.config import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict
from deeplearning4j_tpu.nn.layers.core import OutputLayer, LossLayer
from deeplearning4j_tpu.nn.layers.recurrent import (BaseRecurrentLayer,
                                                    RnnOutputLayer)
from deeplearning4j_tpu.nn.layers.special import FrozenLayer
from deeplearning4j_tpu.nn.multilayer import _FUSABLE
from deeplearning4j_tpu.nn.vertices import (GraphVertex, vertex_from_dict)
from deeplearning4j_tpu.ops import losses as losses_mod
from deeplearning4j_tpu.perf import aot_store, sentry
from deeplearning4j_tpu.resilience import faults


@dataclass
class _Node:
    name: str
    kind: str                  # "layer" | "vertex"
    obj: Any
    inputs: List[str]


class ComputationGraphConfiguration:
    def __init__(self, inputs: List[str], outputs: List[str],
                 nodes: List[_Node], seed: int = 12345,
                 updater=None, dtype: str = "float32",
                 compute_dtype: Optional[str] = None,
                 input_types: Optional[Dict[str, InputType]] = None,
                 gradient_normalization: Optional[str] = None,
                 gradient_normalization_threshold: float = 1.0):
        self.inputs = inputs
        self.outputs = outputs
        self.nodes = nodes
        self.seed = seed
        self.updater = updater or upd.Sgd(learning_rate=1e-2)
        self.dtype = dtype
        self.compute_dtype = compute_dtype
        self.input_types = input_types or {}
        self.gradient_normalization = gradient_normalization
        self.gradient_normalization_threshold = \
            gradient_normalization_threshold

    def to_json(self) -> str:
        return json.dumps({
            "inputs": self.inputs,
            "outputs": self.outputs,
            "nodes": [{"name": n.name, "kind": n.kind,
                       "inputs": n.inputs, "conf": n.obj.to_dict()}
                      for n in self.nodes],
            "seed": self.seed,
            "updater": self.updater.to_dict(),
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "input_types": {k: v.to_dict()
                            for k, v in self.input_types.items()},
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold":
                self.gradient_normalization_threshold,
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        d = json.loads(s)
        nodes = []
        for nd in d["nodes"]:
            obj = (layer_from_dict(nd["conf"]) if nd["kind"] == "layer"
                   else vertex_from_dict(nd["conf"]))
            nodes.append(_Node(nd["name"], nd["kind"], obj, nd["inputs"]))
        return ComputationGraphConfiguration(
            inputs=d["inputs"], outputs=d["outputs"], nodes=nodes,
            seed=d.get("seed", 12345),
            updater=upd.updater_from_dict(d["updater"]),
            dtype=d.get("dtype", "float32"),
            compute_dtype=d.get("compute_dtype"),
            input_types={k: InputType.from_dict(v)
                         for k, v in d.get("input_types", {}).items()},
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get(
                "gradient_normalization_threshold", 1.0))


class GraphBuilder:
    """Reference: ComputationGraphConfiguration.GraphBuilder."""

    def __init__(self, global_conf=None):
        self._g = global_conf
        self._inputs: List[str] = []
        self._outputs: List[str] = []
        self._nodes: List[_Node] = []
        self._input_types: Dict[str, InputType] = {}

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str
                  ) -> "GraphBuilder":
        if self._g is not None:
            from deeplearning4j_tpu.nn.config import _GLOBAL_DEFAULTS
            for attr in _GLOBAL_DEFAULTS:
                if getattr(layer, attr, None) is None:
                    gv = getattr(self._g, attr, None)
                    if gv is not None:
                        setattr(layer, attr, gv)
        layer.name = name
        self._nodes.append(_Node(name, "layer", layer, list(inputs)))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str
                   ) -> "GraphBuilder":
        self._nodes.append(_Node(name, "vertex", vertex, list(inputs)))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs.extend(names)
        return self

    def set_input_types(self, **types: InputType) -> "GraphBuilder":
        self._input_types.update(types)
        return self

    def build(self) -> ComputationGraphConfiguration:
        g = self._g
        return ComputationGraphConfiguration(
            inputs=self._inputs, outputs=self._outputs, nodes=self._nodes,
            seed=g.seed_ if g else 12345,
            updater=g.updater_ if g else None,
            dtype=g.dtype_ if g else "float32",
            compute_dtype=g.compute_dtype_ if g else None,
            input_types=self._input_types,
            gradient_normalization=g.grad_norm_ if g else None,
            gradient_normalization_threshold=(
                g.grad_norm_threshold_ if g else 1.0))


def _group_sig(xs, ys, fms, lms):
    """Grouping key for the scanned device loop: batches scan together
    only when every array shape and the mask structure match."""
    arrs = (list(xs) + list(ys)
            + [m for m in (fms or []) if m is not None]
            + [m for m in (lms or []) if m is not None])
    return (tuple(m is not None for m in (fms or [])),
            tuple(m is not None for m in (lms or [])),
            [np.shape(a) for a in arrs])


def _toposort(nodes: List[_Node], inputs: List[str]) -> List[_Node]:
    done = set(inputs)
    ordered: List[_Node] = []
    pending = list(nodes)
    while pending:
        progressed = False
        for n in list(pending):
            if all(i in done for i in n.inputs):
                ordered.append(n)
                done.add(n.name)
                pending.remove(n)
                progressed = True
        if not progressed:
            missing = {i for n in pending for i in n.inputs} - done
            raise ValueError(f"graph has cycle or missing inputs: "
                             f"{sorted(missing)}")
    return ordered


class ComputationGraph:
    """DAG network (reference ComputationGraph)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.order = _toposort(conf.nodes, conf.inputs)
        self.params: Dict[str, Any] = {}
        self.state: Dict[str, Any] = {}
        self.opt_state = None
        self.listeners: List[Any] = []
        self.iteration = 0
        self.epoch = 0
        self.score_ = float("nan")
        self._train_step_fn = None
        self._train_loop_fn = None
        self._output_fn = None
        self._optimizer = None
        self._shapes: Dict[str, tuple] = {}
        self._numerics = None        # obs.numerics.NumericsMonitor
        self._diag_step_fn = None
        self.last_numerics = None    # last processed diag record

    # ------------------------------------------------------------------
    def init(self, input_shapes: Optional[Dict[str, tuple]] = None):
        shapes: Dict[str, tuple] = {}
        for name in self.conf.inputs:
            if input_shapes and name in input_shapes:
                shapes[name] = tuple(input_shapes[name])
            elif name in self.conf.input_types:
                shapes[name] = self.conf.input_types[name].shape
            else:
                raise ValueError(f"no input shape for {name!r}")
        dtype = dtypes.resolve(self.conf.dtype)
        key = jax.random.PRNGKey(self.conf.seed)
        for node in self.order:
            in_shapes = [shapes[i] for i in node.inputs]
            if node.kind == "layer":
                key, sub = jax.random.split(key)
                p, s, out = node.obj.init(sub, in_shapes[0], dtype)
                self.params[node.name] = p
                self.state[node.name] = s
            else:
                out = node.obj.output_shape(in_shapes)
            shapes[node.name] = out
        self._shapes = shapes
        self._build_optimizer()
        return self

    def _build_optimizer(self):
        transforms, labels = {}, {}
        for node in self.order:
            if node.kind != "layer":
                continue
            layer = node.obj
            frozen = isinstance(layer, FrozenLayer) or not layer.trainable
            if frozen:
                transforms[node.name] = optax.set_to_zero()
            else:
                chain = [upd.gradient_normalization(
                    self.conf.gradient_normalization,
                    self.conf.gradient_normalization_threshold)]
                if layer.weight_decay:
                    chain.append(optax.add_decayed_weights(
                        layer.weight_decay))
                u = layer.updater or self.conf.updater
                chain.append(u.to_optax())
                transforms[node.name] = optax.chain(*chain)
            labels[node.name] = node.name
        self._optimizer = optax.multi_transform(transforms,
                                                param_labels=labels)
        self.opt_state = self._optimizer.init(self.params)

    # ------------------------------------------------------------------
    def _forward(self, params, state, inputs: Dict[str, jax.Array], *,
                 train: bool, rng, masks=None,
                 pre_output: bool = False, stats_out=None):
        acts: Dict[str, jax.Array] = dict(inputs)
        new_state = {}
        masks = dict(masks or {})
        out_set = set(self.conf.outputs)
        for node in self.order:
            xs = [acts[i] for i in node.inputs]
            m = next((masks.get(i) for i in node.inputs
                      if masks.get(i) is not None), None)
            # device-time attribution (obs/devtime.py): trace-time HLO
            # metadata only — the compiled program is byte-identical
            nscope = obs.devtime.scope(
                f"{node.name}.{type(node.obj).__name__}")
            if node.kind == "vertex":
                with nscope:
                    if node.obj.needs_mask:
                        acts[node.name] = node.obj.apply(xs, mask=m)
                    else:
                        acts[node.name] = node.obj.apply(xs)
                masks[node.name] = node.obj.propagate_mask(m)
                continue
            layer = node.obj
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            if (pre_output and node.name in out_set
                    and isinstance(layer, OutputLayer)):
                with nscope:
                    x = xs[0]
                    if x.ndim > 2 and not isinstance(layer,
                                                     RnnOutputLayer):
                        # flatten to [B, features] exactly where
                        # OutputLayer.apply does; RnnOutputLayer.apply
                        # keeps [B, T, F] (per-timestep head)
                        x = x.reshape(x.shape[0], -1)
                    z = x @ params[node.name]["W"]
                    if layer.has_bias:
                        z = z + params[node.name]["b"]
                acts[node.name] = z
                new_state[node.name] = state.get(node.name, {})
                masks[node.name] = m
                if stats_out is not None:
                    stats_out[node.name] = obs.numerics.act_summary(z)
                continue
            with nscope:
                y, s = layer.apply(params.get(node.name, {}),
                                   state.get(node.name, {}), xs[0],
                                   train=train, rng=sub, mask=m)
            acts[node.name] = y
            new_state[node.name] = (state.get(node.name, {})
                                    if isinstance(layer,
                                                  BaseRecurrentLayer)
                                    else s)
            if stats_out is not None:
                # diagnostic step: tap this node's output AS TRACED —
                # scalars become aux outputs of the same XLA program
                stats_out[node.name] = obs.numerics.act_summary(y)
            masks[node.name] = layer.propagate_mask(m, None)
        return acts, new_state

    def _out_loss(self, name):
        node = next(n for n in self.order if n.name == name)
        layer = node.obj
        if hasattr(layer, "compute_loss_fn"):
            # layer-defined loss (e.g. Yolo2OutputLayer) — never fused
            return layer.compute_loss_fn(), False
        loss_name = getattr(layer, "loss", None)
        if loss_name is None:
            raise ValueError(f"output {name!r} has no loss")
        act = (layer.activation or "identity").lower()
        fused = (act, loss_name.lower()) in _FUSABLE and \
            isinstance(layer, OutputLayer)
        return loss_name, fused

    def _apply_weight_noise(self, params, rng):
        """Train-time weight noise per layer node (reference
        WeightNoise / DropConnect)."""
        out = dict(params)
        for node in self.order:
            wn = (getattr(node.obj, "weight_noise", None)
                  if node.kind == "layer" else None)
            if wn is not None and node.name in out:
                rng, sub = jax.random.split(rng)
                out[node.name] = wn.apply(out[node.name], sub)
        return out

    def _apply_constraints(self, params):
        """Post-update parameter constraints (reference LayerConstraint)."""
        out = dict(params)
        for node in self.order:
            cs = (getattr(node.obj, "constraints", None)
                  if node.kind == "layer" else None)
            if cs and node.name in out:
                p = out[node.name]
                for c in cs:
                    p = c.apply(p)
                out[node.name] = p
        return out

    def _has_weight_noise(self):
        return any(node.kind == "layer"
                   and getattr(node.obj, "weight_noise", None) is not None
                   for node in self.order)

    def _loss_fn(self, params, state, inputs, labels, masks, lmasks, rng,
                 act_stats=None):
        any_fused = any(self._out_loss(o)[1] for o in self.conf.outputs)
        cd = self.conf.compute_dtype
        if self._has_weight_noise():
            nrng, rng = jax.random.split(rng)
            params = self._apply_weight_noise(params, nrng)
        if cd is not None:
            # bf16 fwd/bwd, fp32 master params (grads return fp32)
            params = dtypes.cast_float_tree(params, cd)
            inputs = dtypes.cast_float_tree(inputs, cd)
        acts, new_state = self._forward(params, state, inputs, train=True,
                                        rng=rng, masks=masks,
                                        pre_output=any_fused,
                                        stats_out=act_stats)
        total = 0.0
        for name, y in zip(self.conf.outputs, labels):
            loss_name, fused = self._out_loss(name)
            fn = losses_mod.get(loss_name)
            kw = {"from_logits": True} if fused else {}
            lm = lmasks.get(name) if lmasks else None
            logits = acts[name]
            # devtime scope: names each output's loss device share
            with obs.devtime.scope(f"loss.{loss_name}"):
                if cd is not None and losses_mod.wants_f32_logits(
                        fn, fused):
                    logits = logits.astype(jnp.float32)
                total = total + fn(y, logits, mask=lm, **kw)
        return total, new_state

    # ------------------------------------------------------------------
    def _update(self, params, opt_state, state, inputs, labels, masks,
                lmasks, rng):
        """One gradient+optimizer update — the single source of truth
        traced by both the per-batch step and the scanned loop."""
        (loss, new_state), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(params, state, inputs,
                                         labels, masks, lmasks, rng)
        # devtime scope: names the optimizer's device share next to
        # the per-node forward/backward scopes
        with obs.devtime.scope("optimizer.update"):
            updates, opt_state = self._optimizer.update(grads,
                                                        opt_state,
                                                        params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
        return params, opt_state, new_state, loss

    def _make_train_step(self):
        return sentry.jit(self._update,
                          name="ComputationGraph.train_step",
                          donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    # numerics observatory (obs/numerics.py — ARCHITECTURE.md §11)
    # ------------------------------------------------------------------
    def _layer_names(self):
        """Parametrized nodes in topological order — the attribution
        ordering the NaN sentinel scans."""
        return [n.name for n in self.order if n.kind == "layer"]

    def monitor_numerics(self, every: int = 1,
                         histograms: bool = False,
                         raise_on_nonfinite: bool = True):
        """Attach the numerics observatory (see
        ``MultiLayerNetwork.monitor_numerics``)."""
        self._numerics = obs.numerics.NumericsMonitor(
            every=every, histograms=histograms,
            raise_on_nonfinite=raise_on_nonfinite)
        self._diag_step_fn = None   # config is traced into the program
        return self

    def _make_diag_step(self):
        histograms = self._numerics.histograms \
            if self._numerics is not None else False
        layers = self._layer_names()

        def diag_update(params, opt_state, state, inputs, labels,
                        masks, lmasks, rng):
            def lf(p):
                stats = {}
                loss, new_state = self._loss_fn(
                    p, state, inputs, labels, masks, lmasks, rng,
                    act_stats=stats)
                return loss, (new_state, stats)

            (loss, (new_state, act_stats)), grads = jax.value_and_grad(
                lf, has_aux=True)(params)
            updates, new_opt = self._optimizer.update(grads, opt_state,
                                                      params)
            new_params = optax.apply_updates(params, updates)
            new_params = self._apply_constraints(new_params)
            diag = obs.numerics.build_diag(
                new_params, grads, updates, act_stats, layers,
                histograms=histograms)
            # packed: 2 host transfers per diag step instead of ~10
            return (new_params, new_opt, new_state, loss,
                    obs.numerics.pack_diag(diag))

        return sentry.jit(diag_update,
                          name="ComputationGraph.diag_step",
                          donate_argnums=(0, 1, 2))

    def _fit_batch_diag(self, inputs, labels, masks, lmasks, t0):
        """Cadence-gated diagnostic step (see
        ``MultiLayerNetwork._fit_batch_diag``)."""
        if self._diag_step_fn is None:
            self._diag_step_fn = self._make_diag_step()
        rng = jax.random.fold_in(jax.random.PRNGKey(self.conf.seed),
                                 self.iteration)
        t1 = obs.now()
        try:
            self.params, self.opt_state, self.state, loss, diag = \
                self._diag_step_fn(self.params, self.opt_state,
                                   self.state, inputs, labels, masks,
                                   lmasks, rng)
            t2 = obs.now()
            self.score_ = float(loss)   # blocking device sync
        except Exception as e:       # HBM OOM → diagnostic dump
            from deeplearning4j_tpu.utils import crashreport
            if crashreport.is_oom(e):
                path = crashreport.write_memory_crash_dump(self, e)
                if path:
                    raise RuntimeError(
                        f"diagnostic training step ran out of device "
                        f"memory (the numerics aux outputs keep "
                        f"grads+updates alive together — try a "
                        f"sparser cadence); crash dump written to "
                        f"{path}") from e
            raise
        obs.record_step("ComputationGraph.fit", t0, t1, t2, obs.now(),
                        cause=self._call_id)
        self.iteration += 1
        self._numerics.process(self, diag, self._layer_names(),
                               entry="ComputationGraph")
        tl0 = obs.now()
        for l in self.listeners:
            l.iteration_done(self, self.iteration, self.epoch)
        if self.listeners:
            obs.record("ComputationGraph.fit/listeners", tl0,
                       obs.now(), self._call_id)

    def _make_train_loop(self):
        """K train steps per dispatched executable (``lax.scan`` over
        stacked batches) — the idiomatic TPU device loop. Each launch
        through the runtime costs ~10ms of host/dispatch latency that a
        per-batch ``fit`` pays per step; the scanned loop pays it once
        per K steps. Numerically identical to K sequential steps: the
        per-iteration rng keys are precomputed and scanned over.
        Masked batches scan too — the mask stacks are (possibly empty)
        dicts, so each mask structure gets its own trace."""
        def one(carry, batch):
            params, opt_state, state = carry
            inputs, labels, masks, lmasks, rng = batch
            params, opt_state, new_state, loss = self._update(
                params, opt_state, state, inputs, labels, masks,
                lmasks, rng)
            return (params, opt_state, new_state), loss

        def loop(params, opt_state, state, inputs_stack, labels_stack,
                 masks_stack, lmasks_stack, rng_stack):
            (p, o, s), losses = jax.lax.scan(
                one, (params, opt_state, state),
                (inputs_stack, labels_stack, masks_stack, lmasks_stack,
                 rng_stack))
            return p, o, s, losses

        # said so that a warm start loads the loop by a key that
        # needs no trace (perf/aot_store.py)
        return sentry.jit(loop, name="ComputationGraph.train_loop",
                          identity=lambda: aot_store.net_identity(self),
                          donate_argnums=(0, 1, 2))

    def _refresh_ambient_trace(self):
        """Drop jitted caches when the ambient distributed context has
        changed since tracing (see MultiLayerNetwork's counterpart)."""
        if not any(node.kind == "layer"
                   and getattr(node.obj, "sequence_parallel", None)
                   for node in self.order):
            return
        from deeplearning4j_tpu.parallel.mesh import context_epoch
        e = context_epoch()
        if getattr(self, "_ctx_epoch", None) != e:
            self._ctx_epoch = e
            self._train_step_fn = None
            self._train_loop_fn = None
            self._output_fn = None
            self._diag_step_fn = None

    def _fit_group(self, flight):
        """Stage the group of uniformly-shaped batches (same mask
        structure) that ``fit`` has gathered in ``flight.pending`` and
        launch it as one scanned call (see
        ``_make_train_loop``) behind the group in ``flight``, whose
        losses are read only then (``_fit_ahead``); the group launched
        stays in flight. What is in flight is read FIRST where this is
        not its next whole group (another length or signature), where
        a diagnostic step is due in this group, and, between this
        group's staging and its launch, where a listener reads the
        state the group in flight leaves."""
        nm, group = self._numerics, flight.pending
        key = (len(group), _group_sig(*group[0]))
        # the iteration this group WILL start at: a group in flight
        # takes ``self.iteration`` only when it is read
        first = self.iteration + flight.steps()
        diag_due = nm is not None and any(nm.due(first + i)
                                          for i in range(len(group)))
        if diag_due or not flight.takes(key):
            flight.drain()
        if diag_due:
            # a diagnostic step is due inside this group: the scanned
            # loop has no per-step aux outputs, so run the group's
            # batches individually (the cadence path, not the hot one)
            nm.note_group_split(len(group))
            for item in group:
                self._fit_batch(*item)
            return
        start = obs.now()
        faults.inject("step")       # site: step dispatch (resilience/)
        self._refresh_ambient_trace()
        if self._train_loop_fn is None:
            self._train_loop_fn = self._make_train_loop()
        flight.wait_staged()
        # h2d is the staging alone: what came before is ``prep``
        t0 = obs.now()
        inputs = {n: jnp.stack([jnp.asarray(np.asarray(item[0][i]))
                                for item in group])
                  for i, n in enumerate(self.conf.inputs)}
        labels = [jnp.stack([jnp.asarray(np.asarray(item[1][j]))
                             for item in group])
                  for j in range(len(group[0][1]))]
        fms0, lms0 = group[0][2], group[0][3]
        masks = {n: jnp.stack([jnp.asarray(np.asarray(item[2][i]))
                               for item in group])
                 for i, n in enumerate(self.conf.inputs)
                 if fms0 and i < len(fms0) and fms0[i] is not None}
        lmasks = {n: jnp.stack([jnp.asarray(np.asarray(item[3][j]))
                                for item in group])
                  for j, n in enumerate(self.conf.outputs)
                  if lms0 and j < len(lms0) and lms0[j] is not None}
        t1 = obs.now()
        staged_ahead = len(flight)      # staged while a loop ran
        if flight.reads_state():
            flight.drain()
        th = obs.now()
        # the rng stack's small programs are dispatched while the
        # staged bytes are still on their way, under ``dispatch``
        base = jax.random.PRNGKey(self.conf.seed)
        rngs = jnp.stack([jax.random.fold_in(base, first + i)
                          for i in range(len(group))])
        try:
            self.params, self.opt_state, self.state, losses = \
                self._train_loop_fn(self.params, self.opt_state,
                                    self.state, inputs, labels, masks,
                                    lmasks, rngs)
        except Exception as e:       # HBM OOM → diagnostic dump
            from deeplearning4j_tpu.utils import crashreport
            if crashreport.is_oom(e):
                path = crashreport.write_memory_crash_dump(self, e)
                if path:
                    raise RuntimeError(
                        f"scanned train loop ran out of device memory "
                        f"(steps_per_loop={len(group)} stacks the group "
                        f"on device — try a smaller value); crash dump "
                        f"written to {path}") from e
            raise
        staged = (inputs, labels, masks, lmasks)
        flight.groups.append(_fit_ahead.Group(
            losses, staged, key, (start, t0, t1, th, obs.now()),
            {"steps": len(group),
             "bytes": sum(a.nbytes for a in jax.tree.leaves(staged)),
             "iteration": first, "staged_ahead": staged_ahead,
             # launched with the loop before it unread
             "ahead": len(flight)}))
        if len(flight) > 1:
            flight.read()

    #: cause id of the running ``fit`` call's records: its first
    #: iteration number
    _call_id = None

    def fit(self, features, labels=None, *, epochs: int = 1,
            features_masks=None, labels_masks=None,
            steps_per_loop: int = 1):
        """fit(MultiDataSet iterator) | fit([x...], [y...]) | fit(x, y).

        ``features_masks``: sequence aligned with inputs ([B,T] each or
        None); ``labels_masks``: aligned with outputs — reference
        MultiDataSet mask semantics (per-position loss masking, e.g.
        MLM masked positions).

        ``steps_per_loop=k > 1`` runs ``k`` uniformly-shaped batches a
        dispatched executable and keeps ONE such group in flight
        (``_fit_ahead``): the iterator is pulled one group ahead of the
        listeners; a listener that does not say ``reads_state`` may
        find ``net.params`` one group newer than the iteration it is
        told; ``fit`` returns, and raises, with nothing in flight."""
        # no frame is added around the loop for the call's record: the
        # time jax takes to lower ``fit``'s program moves by seconds
        # with the Python stack it is traced under (PERF.md, PR 24).
        # Not with its depth alone: with the WORDS its frames hold.
        # CPython frees a 16 KiB chunk of its frame stack as soon as
        # the chunk's first frame returns, so a hot loop of the tracer
        # whose callees fall just over a chunk's end pays an mmap and
        # a munmap a call; five more locals in ``fit``, ``_flush_group``
        # and ``_fit_group`` together doubled the loop's lowering
        # (PERF.md, PR 42: the three hold 80 words, as they did)
        tc0 = obs.now()
        self._call_id = self.iteration   # cause of this call's records
        if labels is not None:
            xs = features if isinstance(features, (list, tuple)) \
                else [features]
            ys = labels if isinstance(labels, (list, tuple)) else [labels]
            self._fit_batch(xs, ys, features_masks, labels_masks)
            obs.record("ComputationGraph.fit/call", tc0, obs.now(),
                       self._call_id)
            return self
        it = features
        flight = _fit_ahead.Flight(self, "ComputationGraph.fit",
                                   self._call_id)
        try:
            for _ in range(epochs):
                for l in self.listeners:
                    l.on_epoch_start(self)
                if hasattr(it, "reset"):
                    it.reset()
                prev_sig = None
                src = iter(it)
                while True:
                    te0 = obs.now()     # iterator wait = ETL attribution
                    try:
                        mds = next(src)
                    except StopIteration:
                        break
                    obs.record_etl("ComputationGraph.fit", te0,
                                   obs.now(), self._call_id)
                    if hasattr(mds, "features"):
                        xs = (mds.features
                              if isinstance(mds.features, list)
                              else [mds.features])
                        ys = (mds.labels
                              if isinstance(mds.labels, list)
                              else [mds.labels])
                        fms = getattr(mds, "features_masks", None)
                        lms = getattr(mds, "labels_masks", None)
                    else:
                        xs, ys = mds
                        xs = xs if isinstance(xs, list) else [xs]
                        ys = ys if isinstance(ys, list) else [ys]
                        fms = lms = None
                    if steps_per_loop > 1:
                        # group uniformly-shaped batches (masks
                        # included — masked BERT batches keep the
                        # device loop) into one scanned call; a shape
                        # or mask-structure change flushes the group
                        sig = _group_sig(xs, ys, fms, lms)
                        if flight.pending and sig != prev_sig:
                            self._flush_group(flight)
                        flight.pending.append((xs, ys, fms, lms))
                        prev_sig = sig
                        if len(flight.pending) == steps_per_loop:
                            self._flush_group(flight)
                    else:
                        self._flush_group(flight)
                        self._fit_batch(xs, ys, fms, lms)
                self._flush_group(flight)
                flight.drain()      # an epoch ends with nothing in flight
                for l in self.listeners:
                    l.on_epoch_end(self)
                self.epoch += 1
        except BaseException:
            flight.settle()         # read what is in flight, then raise
            raise
        obs.record("ComputationGraph.fit/call", tc0, obs.now(),
                   self._call_id)
        return self

    def _flush_group(self, flight):
        if len(flight.pending) > 1:
            self._fit_group(flight)
        else:
            # a single batch is no next group: it runs alone
            flight.drain()
            if flight.pending:
                self._fit_batch(*flight.pending[0])
        flight.pending.clear()

    def _fit_batch(self, xs, ys, fms=None, lms=None):
        t0 = obs.now()
        faults.inject("step")       # site: step dispatch (resilience/)
        self._refresh_ambient_trace()
        if self._train_step_fn is None:
            self._train_step_fn = self._make_train_step()
        inputs = {n: jnp.asarray(np.asarray(x))
                  for n, x in zip(self.conf.inputs, xs)}
        labels = [jnp.asarray(np.asarray(y)) for y in ys]
        masks = {n: jnp.asarray(np.asarray(m))
                 for n, m in zip(self.conf.inputs, fms or [])
                 if m is not None}
        lmasks = {n: jnp.asarray(np.asarray(m))
                  for n, m in zip(self.conf.outputs, lms or [])
                  if m is not None}
        nm = self._numerics     # off path: one attribute check
        if nm is not None and nm.due(self.iteration):
            return self._fit_batch_diag(inputs, labels, masks, lmasks,
                                        t0)
        # devtime + commtime capture windows (obs/devtime.py,
        # obs/commtime.py): off path is one module-global branch
        # inside each hook
        obs.devtime.step_started(self.iteration)
        obs.commtime.step_started(self.iteration)
        rng = jax.random.fold_in(jax.random.PRNGKey(self.conf.seed),
                                 self.iteration)
        t1 = obs.now()
        self.params, self.opt_state, self.state, loss = \
            self._train_step_fn(self.params, self.opt_state, self.state,
                                inputs, labels, masks, lmasks, rng)
        t2 = obs.now()
        self.score_ = float(loss)     # blocking device sync
        obs.devtime.step_ended(self._train_step_fn)
        obs.commtime.step_ended(self._train_step_fn)
        obs.record_step("ComputationGraph.fit", t0, t1, t2, obs.now(),
                        cause=self._call_id)
        self.iteration += 1
        if nm is not None:
            nm.note_score(self.score_)
        tl0 = obs.now()
        for l in self.listeners:
            l.iteration_done(self, self.iteration, self.epoch)
        if self.listeners:
            obs.record("ComputationGraph.fit/listeners", tl0,
                       obs.now(), self._call_id)

    # ------------------------------------------------------------------
    def _make_output_fn(self):
        cd = self.conf.compute_dtype

        def infer(params, state, inputs):
            if cd is not None:
                params = dtypes.cast_float_tree(params, cd)
                state = dtypes.cast_float_tree(state, cd)
                inputs = dtypes.cast_float_tree(inputs, cd)
            acts, _ = self._forward(params, state, inputs,
                                    train=False, rng=None)
            outs = [acts[o] for o in self.conf.outputs]
            if cd is not None:
                outs = [o.astype(jnp.float32) for o in outs]
            return outs

        return sentry.jit(infer, name="ComputationGraph.output")

    def output(self, *features, train: bool = False):
        """Returns a list of output activations (reference
        ComputationGraph.output)."""
        self._refresh_ambient_trace()
        if self._output_fn is None:
            self._output_fn = self._make_output_fn()
        inputs = {n: jnp.asarray(np.asarray(x))
                  for n, x in zip(self.conf.inputs, features)}
        return self._output_fn(self.params, self.state, inputs)

    def warmup(self, specs):
        """AOT-compile the train step, scanned loop, and output fn for
        every declared shape bucket (see ``perf.warmup``)."""
        from deeplearning4j_tpu.perf.warmup import warmup_network
        self._refresh_ambient_trace()
        return warmup_network(self, specs)

    def output_single(self, *features):
        return self.output(*features)[0]

    def score(self, dataset=None) -> float:
        return self.score_

    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval_.evaluation import Evaluation
        e = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            x, y = (ds.features, ds.labels) if hasattr(ds, "features") \
                else ds
            out = self.output(x)[0]
            e.eval(np.asarray(y), np.asarray(out))
        return e

    def num_params(self) -> int:
        return sum(int(np.prod(np.shape(l)))
                   for l in jax.tree.leaves(self.params))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def summary(self) -> str:
        lines = ["=" * 76,
                 f"{'Node':<24}{'Type':<26}{'Output':<16}{'Params':>8}",
                 "=" * 76]
        total = 0
        for node in self.order:
            n = 0
            if node.kind == "layer":
                n = sum(int(np.prod(np.shape(l))) for l in
                        jax.tree.leaves(self.params[node.name]))
            total += n
            lines.append(
                f"{node.name:<24}{type(node.obj).__name__:<26}"
                f"{str(self._shapes.get(node.name)):<16}{n:>8,}")
        lines.append("=" * 76)
        lines.append(f"Total params: {total:,}")
        return "\n".join(lines)

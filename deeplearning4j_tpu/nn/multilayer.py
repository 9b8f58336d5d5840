"""MultiLayerNetwork — reference:
``org.deeplearning4j.nn.multilayer.MultiLayerNetwork`` (~4k-line class,
SURVEY §2.3/§3.2).

TPU-native redesign: instead of the reference's per-op eager dispatch
(layer.activate → JNI → kernel, one crossing per op), the WHOLE training
step — forward, loss, backward, updater, param update — is one traced
``jax.jit`` computation: XLA fuses it and keeps everything in HBM.
``fit`` then just streams batches into the compiled step.

Supports: fit/output/score, masks, truncated BPTT with stored recurrent
state (reference rnnTimeStep / rnnActivateUsingStoredState), listeners,
per-layer updater/LR overrides, frozen layers, l1/l2/weight-decay,
gradient normalization modes.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu import dtypes
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.nn import _fit_ahead
from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.layers.core import OutputLayer, LossLayer
from deeplearning4j_tpu.nn.layers.recurrent import (
    BaseRecurrentLayer, RnnOutputLayer, RnnLossLayer)
from deeplearning4j_tpu.nn.layers.special import FrozenLayer
from deeplearning4j_tpu.nn import updaters as upd
from deeplearning4j_tpu.ops import losses as losses_mod
from deeplearning4j_tpu.perf import aot_store, sentry
from deeplearning4j_tpu.resilience import faults

# losses that support the fused from_logits path, keyed by activation
_FUSABLE = {
    ("softmax", "mcxent"), ("softmax", "negativeloglikelihood"),
    ("softmax", "sparse_mcxent"), ("sigmoid", "xent"),
    ("sigmoid", "binary_xent"),
}


def _lname(i: int) -> str:
    return f"layer_{i}"


class MultiLayerNetwork:
    """Sequential stack model (reference MultiLayerNetwork)."""

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers: List[Layer] = conf.layers
        self.params: Dict[str, Any] = {}
        self.state: Dict[str, Any] = {}
        self.opt_state = None
        self.listeners: List[Any] = []
        self.iteration = 0
        self.epoch = 0
        self._rnn_state: Optional[Dict[str, Any]] = None  # stored-state API
        self._train_step_fn = None
        self._train_loop_fn = None
        self._output_fn = None
        self._optimizer = None
        self.score_ = float("nan")
        self._numerics = None        # obs.numerics.NumericsMonitor
        self._diag_step_fn = None
        self.last_numerics = None    # last processed diag record

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, input_shape: Optional[Tuple[int, ...]] = None):
        """Build params (reference MultiLayerNetwork.init()). Shape comes
        from conf.input_type unless given explicitly (no batch dim)."""
        if input_shape is None:
            if self.conf.input_type is None:
                raise ValueError("init() needs input_shape or "
                                 "conf.input_type")
            input_shape = self.conf.input_type.shape
            if self.conf.input_type.kind == "rnn" and input_shape[0] == -1:
                input_shape = (None,) + input_shape[1:]
        dtype = dtypes.resolve(self.conf.dtype)
        key = jax.random.PRNGKey(self.conf.seed)
        shape = tuple(input_shape)
        self._input_shape = shape
        self._layer_shapes = []
        for i, layer in enumerate(self.layers):
            proc = self.conf.input_preprocessors.get(i)
            if proc is not None:
                shape = proc.output_shape(shape)
            key, sub = jax.random.split(key)
            p, s, shape = layer.init(sub, shape, dtype)
            self.params[_lname(i)] = p
            self.state[_lname(i)] = s
            self._layer_shapes.append(shape)
        self._output_shape = shape
        # tied params are NOT master parameters: drop them after init
        # (shape-checked against their source); _forward rebuilds them
        for di, dn, si, sn, tr in self.conf.tied_weights:
            src = self.params[_lname(si)][sn]
            dst = self.params[_lname(di)].pop(dn)
            want = src.shape[::-1] if tr else src.shape
            if tuple(dst.shape) != tuple(want):
                raise ValueError(
                    f"tie_weights: layer_{di}.{dn} {dst.shape} != "
                    f"layer_{si}.{sn}{'(transposed)' if tr else ''} "
                    f"{want}")
        self._build_optimizer()
        return self

    def _materialize_ties(self, params):
        """Rebuild tied params from their source inside the traced
        forward — gradients accumulate onto the source from both
        uses."""
        ties = getattr(self.conf, "tied_weights", None)
        if not ties:
            return params
        out = dict(params)
        for di, dn, si, sn, tr in ties:
            src = out[_lname(si)][sn]
            blk = dict(out.get(_lname(di), {}))
            blk[dn] = src.T if tr else src
            out[_lname(di)] = blk
        return out

    def _layer_updater(self, layer: Layer):
        u = layer.updater
        if u is None and layer.learning_rate is not None:
            import copy
            u = copy.deepcopy(self.conf.updater)
            u.learning_rate = layer.learning_rate
            u.schedule = None
        return u or self.conf.updater

    def _build_optimizer(self):
        transforms, labels = {}, {}
        for i, layer in enumerate(self.layers):
            name = _lname(i)
            frozen = isinstance(layer, FrozenLayer) or not layer.trainable
            if frozen:
                transforms[name] = optax.set_to_zero()
            else:
                chain = [upd.gradient_normalization(
                    self.conf.gradient_normalization,
                    self.conf.gradient_normalization_threshold)]
                if layer.weight_decay:
                    chain.append(optax.add_decayed_weights(
                        layer.weight_decay))
                chain.append(self._layer_updater(layer).to_optax())
                transforms[name] = optax.chain(*chain)
            labels[name] = name
        self._optimizer = optax.multi_transform(
            transforms, param_labels=labels)
        self.opt_state = self._optimizer.init(self.params)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _forward(self, params, state, x, *, train, rng, mask=None,
                 rnn_init=None, stop_at: Optional[int] = None,
                 pre_output_last: bool = False, stats_out=None):
        """Returns (activation, new_state, rnn_states)."""
        if not params:
            raise RuntimeError(
                "Network has no parameters — call init() before "
                "fit()/output() (reference: MultiLayerNetwork.init()).")
        params = self._materialize_ties(params)
        new_state = {}
        rnn_states = {}
        n = len(self.layers) if stop_at is None else stop_at
        preprocs = self.conf.input_preprocessors
        for i in range(n):
            layer = self.layers[i]
            name = _lname(i)
            proc = preprocs.get(i)
            if proc is not None:
                x = proc.pre_process(x)
                mask = proc.propagate_mask(mask)
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            kwargs = {}
            if isinstance(layer, BaseRecurrentLayer) and rnn_init:
                kwargs["initial_state"] = rnn_init.get(name)
            # device-time attribution (obs/devtime.py): the scope is
            # trace-time HLO metadata only — the compiled step is
            # byte-identical; jax carries it into the backward ops as
            # transpose(jvp(<scope>)), so gradients attribute too
            lscope = obs.devtime.scope(f"{name}.{type(layer).__name__}")
            if (pre_output_last and i == n - 1
                    and isinstance(layer, (OutputLayer,))):
                # pre-activation logits for fused loss
                with lscope:
                    z = x.reshape(x.shape[0], -1) if (
                        not isinstance(layer, RnnOutputLayer)
                        and x.ndim > 2
                    ) else x
                    z = z @ params[name]["W"]
                    if layer.has_bias:
                        z = z + params[name]["b"]
                x = z
                new_state[name] = state.get(name, {})
                if stats_out is not None:
                    stats_out[name] = obs.numerics.act_summary(x)
                continue
            with lscope:
                x, s = layer.apply(params.get(name, {}),
                                   state.get(name, {}),
                                   x, train=train, rng=sub, mask=mask,
                                   **kwargs)
            if isinstance(layer, BaseRecurrentLayer):
                rnn_states[name] = s
                new_state[name] = state.get(name, {})
            else:
                new_state[name] = s
            if stats_out is not None:
                # diagnostic step: tap this layer's output AS TRACED —
                # scalars become aux outputs of the same XLA program
                stats_out[name] = obs.numerics.act_summary(x)
            mask = layer.propagate_mask(mask, None)
        return x, new_state, rnn_states

    # ------------------------------------------------------------------
    # loss
    # ------------------------------------------------------------------
    def _last_loss(self):
        last = self.layers[-1]
        if hasattr(last, "compute_loss_fn"):
            # layer-defined loss (e.g. Yolo2OutputLayer) — never fused
            return last.compute_loss_fn(), False
        loss_name = getattr(last, "loss", None)
        if loss_name is None:
            raise ValueError("last layer has no loss; use an OutputLayer/"
                             "LossLayer variant for fit()")
        act = (last.activation or "identity").lower()
        # the fused pre-activation shortcut in _forward only handles
        # OutputLayer — a LossLayer applies its activation in-layer
        fused = (act, loss_name.lower()) in _FUSABLE and \
            isinstance(last, OutputLayer)
        return loss_name, fused

    def _reg_score(self, params):
        total = 0.0
        for i, layer in enumerate(self.layers):
            l1v, l2v = layer.l1, layer.l2
            if not l1v and not l2v:
                continue
            for leaf in jax.tree.leaves(params[_lname(i)]):
                if l1v:
                    total = total + l1v * jnp.sum(jnp.abs(leaf))
                if l2v:
                    total = total + 0.5 * l2v * jnp.sum(jnp.square(leaf))
        return total

    def _apply_weight_noise(self, params, rng):
        """Train-time weight noise per layer (reference WeightNoise /
        DropConnect, conf.weightnoise) — perturbs the forward's view of
        the params; the master params are untouched."""
        out = dict(params)
        for i, layer in enumerate(self.layers):
            wn = getattr(layer, "weight_noise", None)
            if wn is not None and _lname(i) in out:
                rng, sub = jax.random.split(rng)
                out[_lname(i)] = wn.apply(out[_lname(i)], sub)
        return out

    def _apply_constraints(self, params):
        """Post-update parameter constraints per layer (reference
        LayerConstraint, applied after the updater step)."""
        out = dict(params)
        for i, layer in enumerate(self.layers):
            cs = getattr(layer, "constraints", None)
            if cs and _lname(i) in out:
                p = out[_lname(i)]
                for c in cs:
                    p = c.apply(p)
                out[_lname(i)] = p
        return out

    def _loss_fn(self, params, state, x, y, mask, lmask, rng,
                 act_stats=None):
        loss_name, fused = self._last_loss()
        cd = self.conf.compute_dtype
        master = params
        if any(getattr(l, "weight_noise", None) is not None
               for l in self.layers):
            nrng, rng = jax.random.split(rng)
            params = self._apply_weight_noise(params, nrng)
        if cd is not None:
            # bf16 fwd/bwd, fp32 master params: the cast is inside the
            # grad trace, so grads come back fp32 for the optimizer
            params = dtypes.cast_float_tree(params, cd)
            x = dtypes.cast_float_tree(x, cd)
        out, new_state, _ = self._forward(
            params, state, x, train=True, rng=rng, mask=mask,
            pre_output_last=fused, stats_out=act_stats)
        loss_fn = losses_mod.get(loss_name)
        # devtime scope: names the loss+regularization device share
        with obs.devtime.scope(f"loss.{loss_name}"):
            if cd is not None and losses_mod.wants_f32_logits(loss_fn,
                                                              fused):
                out = out.astype(jnp.float32)
            kw = {"from_logits": True} if fused else {}
            data_loss = loss_fn(y, out, mask=lmask, **kw)
            total = data_loss + self._reg_score(master)
        return total, new_state

    # ------------------------------------------------------------------
    # fit
    # ------------------------------------------------------------------
    def _update(self, params, opt_state, state, x, y, mask, lmask, rng):
        """One gradient+optimizer update — the single source of truth
        traced by both the per-batch step and the scanned loop."""
        (loss, new_state), grads = jax.value_and_grad(
            self._loss_fn, has_aux=True)(
                params, state, x, y, mask, lmask, rng)
        # devtime scope: names the optimizer's device share next to
        # the per-layer forward/backward scopes
        with obs.devtime.scope("optimizer.update"):
            updates, opt_state = self._optimizer.update(grads,
                                                        opt_state,
                                                        params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
        return params, opt_state, new_state, loss

    def _make_train_step(self):
        return sentry.jit(self._update,
                          name="MultiLayerNetwork.train_step",
                          donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    # numerics observatory (obs/numerics.py — ARCHITECTURE.md §11)
    # ------------------------------------------------------------------
    def _layer_names(self):
        return [_lname(i) for i in range(len(self.layers))]

    def monitor_numerics(self, every: int = 1,
                         histograms: bool = False,
                         raise_on_nonfinite: bool = True):
        """Attach the numerics observatory: every ``every``-th step is
        a *diagnostic step* — a second compiled variant of the train
        step whose aux outputs are per-layer gradient/update/param
        norms, activation stats from the real training forward, and
        the non-finite sentinel (see ``obs/numerics.py``). Off the
        cadence, the default step runs untouched."""
        self._numerics = obs.numerics.NumericsMonitor(
            every=every, histograms=histograms,
            raise_on_nonfinite=raise_on_nonfinite)
        self._diag_step_fn = None   # config is traced into the program
        return self

    def _make_diag_step(self):
        histograms = self._numerics.histograms \
            if self._numerics is not None else False
        layers = self._layer_names()

        def diag_update(params, opt_state, state, x, y, mask, lmask,
                        rng):
            def lf(p):
                stats = {}
                loss, new_state = self._loss_fn(
                    p, state, x, y, mask, lmask, rng, act_stats=stats)
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
                          name="MultiLayerNetwork.diag_step",
                          donate_argnums=(0, 1, 2))

    def _fit_batch_diag(self, x, y, fmask, lmask, t0):
        """Cadence-gated diagnostic step: same update, plus the
        numerics aux outputs (scalars-only host pull at cadence)."""
        if self._diag_step_fn is None:
            self._diag_step_fn = self._make_diag_step()
        rng = jax.random.fold_in(jax.random.PRNGKey(self.conf.seed),
                                 self.iteration)
        t1 = obs.now()
        try:
            self.params, self.opt_state, self.state, loss, diag = \
                self._diag_step_fn(self.params, self.opt_state,
                                   self.state, x, y, fmask, lmask, rng)
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
        obs.record_step("MultiLayerNetwork.fit", t0, t1, t2, obs.now())
        self.iteration += 1
        # publishes gauges/trace counters and raises NonFiniteError
        # naming the origin layer when the sentinel fired
        self._numerics.process(self, diag, self._layer_names(),
                               entry="MultiLayerNetwork")
        tl0 = obs.now()
        for l in self.listeners:
            l.iteration_done(self, self.iteration, self.epoch)
        if self.listeners:
            obs.record("MultiLayerNetwork.fit/listeners", tl0,
                       obs.now())

    def _make_train_loop(self):
        """K train steps per dispatched executable (``lax.scan`` over
        stacked batches) — see ComputationGraph._make_train_loop.
        Numerically identical to K sequential ``fit`` calls (same
        per-iteration rng fold_in scheme)."""
        def one(carry, batch):
            params, opt_state, state = carry
            x, y, rng = batch
            params, opt_state, new_state, loss = self._update(
                params, opt_state, state, x, y, None, None, rng)
            return (params, opt_state, new_state), loss

        def loop(params, opt_state, state, x_stack, y_stack, rng_stack):
            (p, o, s), losses = jax.lax.scan(
                one, (params, opt_state, state),
                (x_stack, y_stack, rng_stack))
            return p, o, s, losses

        # said so that a warm start loads the loop by a key that
        # needs no trace (perf/aot_store.py)
        return sentry.jit(loop, name="MultiLayerNetwork.train_loop",
                          identity=lambda: aot_store.net_identity(self),
                          donate_argnums=(0, 1, 2))

    def _refresh_ambient_trace(self):
        """Nets whose layers consult the ambient distributed context
        (``sequence_parallel`` attention) bake that decision into their
        jitted traces — drop the caches whenever the context has
        changed since tracing, so entering/exiting
        ``parallel.distributed_context`` never runs a stale plan."""
        if not any(getattr(l, "sequence_parallel", None)
                   for l in self.layers):
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
        """Stage the group of uniformly-shaped batches gathered in
        ``flight.pending`` and launch it as one scanned call behind
        the group in flight (see
        ``ComputationGraph._fit_group``). An armed ``devtime`` or
        ``commtime`` capture window brackets a loop from its start to
        its blocking read, so under one every group runs alone."""
        nm, group = self._numerics, flight.pending
        key = (len(group), np.shape(group[0][0]), np.shape(group[0][1]))
        # the iteration this group WILL start at: a group in flight
        # takes ``self.iteration`` only when it is read
        first = self.iteration + flight.steps()
        diag_due = nm is not None and any(nm.due(first + i)
                                          for i in range(len(group)))
        alone = (obs.devtime._MONITOR is not None
                 or obs.commtime._MONITOR is not None)
        if diag_due or alone or not flight.takes(key):
            flight.drain()
        if diag_due:
            # a diagnostic step is due inside this group: the scanned
            # loop has no per-step aux outputs, so run the group's
            # batches individually (the cadence path, not the hot one)
            nm.note_group_split(len(group))
            for x, y in group:
                self._fit_batch(x, y)
            return
        start = obs.now()
        faults.inject("step")       # site: step dispatch (resilience/)
        self._refresh_ambient_trace()
        if self._train_loop_fn is None:
            self._train_loop_fn = self._make_train_loop()
        obs.devtime.step_started(first)
        obs.commtime.step_started(first)
        flight.wait_staged()
        # h2d is the staging alone: what came before is ``prep``
        t0 = obs.now()
        xs = jnp.stack([jnp.asarray(np.asarray(x)) for x, _ in group])
        ys = jnp.stack([jnp.asarray(np.asarray(y)) for _, y in group])
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
                                    self.state, xs, ys, rngs)
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
        flight.groups.append(_fit_ahead.Group(
            losses, (xs, ys), key, (start, t0, t1, th, obs.now()),
            {"steps": len(group), "bytes": xs.nbytes + ys.nbytes,
             "iteration": first, "staged_ahead": staged_ahead,
             # launched with the loop before it unread
             "ahead": len(flight)}))
        if alone or len(flight) > 1:
            flight.read()       # alone: this group's own, at once
        if alone:
            obs.devtime.step_ended(self._train_loop_fn)
            obs.commtime.step_ended(self._train_loop_fn)

    def _flush_group(self, flight):
        if len(flight.pending) > 1:
            self._fit_group(flight)
        else:
            # a single batch is no next group: it runs alone
            flight.drain()
            if flight.pending:
                self._fit_batch(*flight.pending[0])
        flight.pending.clear()

    def fit(self, features, labels=None, *, epochs: int = 1,
            features_mask=None, labels_mask=None, steps_per_loop: int = 1):
        """fit(x, y) for one batch, or fit(iterator, epochs=N).

        Iterator elements: DataSet-like (``.features``/``.labels``/
        ``.features_mask``/``.labels_mask``) or (x, y) tuples.
        Reference: MultiLayerNetwork.fit(DataSetIterator) — SURVEY §3.2.
        ``steps_per_loop``: batches are grouped and run K steps per
        dispatched executable (scanned device loop) — amortises
        host/dispatch latency; mask-free uniformly-shaped batches only.
        ONE such group is kept in flight (``_fit_ahead``): the iterator
        is pulled one group ahead of the listeners; a listener that
        does not say ``reads_state`` may find ``net.params`` one group
        newer than the iteration it is told; ``fit`` returns, and
        raises, with nothing in flight.
        """
        if labels is not None:
            self._fit_batch(features, labels, features_mask, labels_mask)
            return self
        if hasattr(features, "features") and not hasattr(features,
                                                         "__iter__"):
            ds = features           # fit(DataSet) — reference API
            self._fit_batch(ds.features, ds.labels,
                            getattr(ds, "features_mask", None),
                            getattr(ds, "labels_mask", None))
            return self
        it = features
        flight = _fit_ahead.Flight(self, "MultiLayerNetwork.fit")
        try:
            for _ in range(epochs):
                for l in self.listeners:
                    l.on_epoch_start(self)
                if hasattr(it, "reset"):
                    it.reset()
                src = iter(it)
                while True:
                    te0 = obs.now()     # iterator wait = ETL attribution
                    try:
                        ds = next(src)
                    except StopIteration:
                        break
                    obs.record_etl("MultiLayerNetwork.fit", te0,
                                   obs.now())
                    if hasattr(ds, "features"):
                        x, y = ds.features, ds.labels
                        fm = getattr(ds, "features_mask", None)
                        lm = getattr(ds, "labels_mask", None)
                    else:
                        x, y = ds
                        fm = lm = None
                    tbptt = (self.conf.backprop_type == "TruncatedBPTT"
                             and np.ndim(x) == 3)
                    if steps_per_loop > 1 and fm is None and lm is None \
                            and not tbptt:
                        group = flight.pending
                        if group and (
                                np.shape(group[-1][0]) != np.shape(x)
                                or np.shape(group[-1][1]) != np.shape(y)):
                            self._flush_group(flight)
                        group.append((x, y))
                        if len(group) == steps_per_loop:
                            self._flush_group(flight)
                    else:
                        self._flush_group(flight)
                        self._fit_batch(x, y, fm, lm)
                self._flush_group(flight)
                flight.drain()      # an epoch ends with nothing in flight
                for l in self.listeners:
                    l.on_epoch_end(self)
                self.epoch += 1
        except BaseException:
            flight.settle()         # read what is in flight, then raise
            raise
        return self

    def _fit_batch(self, x, y, fmask=None, lmask=None):
        t0 = obs.now()
        faults.inject("step")       # site: step dispatch (resilience/)
        x = jnp.asarray(np.asarray(x))
        y = jnp.asarray(np.asarray(y))
        if (self.conf.backprop_type == "TruncatedBPTT" and x.ndim == 3):
            return self._fit_tbptt(x, y, fmask, lmask, _t0=t0)
        self._refresh_ambient_trace()
        nm = self._numerics     # off path: one attribute check
        if nm is not None and nm.due(self.iteration):
            return self._fit_batch_diag(x, y, fmask, lmask, t0)
        if self._train_step_fn is None:
            self._train_step_fn = self._make_train_step()
        # devtime + commtime capture windows (obs/devtime.py,
        # obs/commtime.py): off path is one module-global branch
        # inside each hook
        obs.devtime.step_started(self.iteration)
        obs.commtime.step_started(self.iteration)
        rng = jax.random.fold_in(jax.random.PRNGKey(self.conf.seed),
                                 self.iteration)
        t1 = obs.now()
        try:
            self.params, self.opt_state, self.state, loss = \
                self._train_step_fn(self.params, self.opt_state,
                                    self.state, x, y, fmask, lmask, rng)
            t2 = obs.now()
            self.score_ = float(loss)   # blocking device sync
            obs.devtime.step_ended(self._train_step_fn)
            obs.commtime.step_ended(self._train_step_fn)
        except Exception as e:       # HBM OOM → diagnostic dump
            from deeplearning4j_tpu.utils import crashreport
            if crashreport.is_oom(e):
                path = crashreport.write_memory_crash_dump(self, e)
                if path:
                    raise RuntimeError(
                        f"training step ran out of device memory; "
                        f"crash dump written to {path}") from e
            raise
        obs.record_step("MultiLayerNetwork.fit", t0, t1, t2, obs.now())
        self.iteration += 1
        if nm is not None:
            nm.note_score(self.score_)
        tl0 = obs.now()
        for l in self.listeners:
            l.iteration_done(self, self.iteration, self.epoch)
        if self.listeners:
            obs.record("MultiLayerNetwork.fit/listeners", tl0,
                       obs.now())

    # -- truncated BPTT (reference: fit segments of tbpttLength, carrying
    #    rnn state across segments; MultiLayerNetwork truncated-BPTT path)
    def _fit_tbptt(self, x, y, fmask, lmask, _t0=None):
        t0 = obs.now() if _t0 is None else _t0
        t1 = obs.now()
        k = self.conf.tbptt_fwd_length
        t = x.shape[1]
        rnn_states = None
        if self._tbptt_step_fn_ is None:
            self._tbptt_step_fn_ = self._make_tbptt_step()
        rng = jax.random.fold_in(jax.random.PRNGKey(self.conf.seed),
                                 self.iteration)
        scannable = (t % k == 0 and t // k > 1 and y.ndim == 3
                     and fmask is None and lmask is None)
        if scannable:
            # segment 0 with the plain step (also yields the rnn-state
            # pytree structure), remaining segments in ONE scanned
            # executable — a T=200/k=50 batch costs 2 dispatches, not 4
            (self.params, self.opt_state, self.state, rnn_states,
             loss) = self._tbptt_step_fn_(
                self.params, self.opt_state, self.state, None,
                x[:, :k], y[:, :k], None, None, rng)
            if self._tbptt_loop_fn_ is None:
                step_fn = self._tbptt_step_fn_

                def seg(carry, batch):
                    params, opt_state, state, rnn, key = carry
                    xs, ys = batch
                    params, opt_state, state, rnn, loss = step_fn(
                        params, opt_state, state, rnn, xs, ys, None,
                        None, key)
                    return (params, opt_state, state, rnn, key), loss

                def loop(params, opt_state, state, rnn, xstack, ystack,
                         key):
                    (p, o, s, r, _), losses = jax.lax.scan(
                        seg, (params, opt_state, state, rnn, key),
                        (xstack, ystack))
                    return p, o, s, r, losses[-1]
                self._tbptt_loop_fn_ = sentry.jit(
                    loop, name="MultiLayerNetwork.tbptt_loop",
                    donate_argnums=(0, 1, 2))
            n_seg = t // k - 1
            xstack = jnp.swapaxes(
                x[:, k:].reshape(x.shape[0], n_seg, k, *x.shape[2:]),
                0, 1)
            ystack = jnp.swapaxes(
                y[:, k:].reshape(y.shape[0], n_seg, k, *y.shape[2:]),
                0, 1)
            (self.params, self.opt_state, self.state, rnn_states,
             loss) = self._tbptt_loop_fn_(
                self.params, self.opt_state, self.state, rnn_states,
                xstack, ystack, rng)
        else:
            loss = None
            for s0 in range(0, t, k):
                xs = x[:, s0:s0 + k]
                ys = y[:, s0:s0 + k] if y.ndim == 3 else y
                fs = fmask[:, s0:s0 + k] if fmask is not None else None
                ls = lmask[:, s0:s0 + k] if lmask is not None else None
                (self.params, self.opt_state, self.state, rnn_states,
                 loss) = self._tbptt_step_fn_(
                    self.params, self.opt_state, self.state, rnn_states,
                    xs, ys, fs, ls, rng)
                # segments stay enqueued on device (no per-segment sync)
        t2 = obs.now()
        self.score_ = float(loss)      # one device->host sync per batch
        obs.record_step("MultiLayerNetwork.fit_tbptt", t0, t1, t2,
                        obs.now())
        self.iteration += 1
        if self._numerics is not None:   # tbptt has no diag variant:
            self._numerics.note_score(self.score_)   # escalation only
        tl0 = obs.now()
        for l in self.listeners:
            l.iteration_done(self, self.iteration, self.epoch)
        if self.listeners:
            obs.record("MultiLayerNetwork.fit/listeners", tl0,
                       obs.now())

    _tbptt_step_fn_ = None
    _tbptt_loop_fn_ = None

    def _make_tbptt_step(self):
        optimizer = self._optimizer
        loss_name, fused = self._last_loss()
        loss_fn = losses_mod.get(loss_name)

        cd = self.conf.compute_dtype

        def loss_with_state(params, state, rnn_init, x, y, mask, lmask,
                            rng):
            master = params
            if any(getattr(l, "weight_noise", None) is not None
                   for l in self.layers):
                nrng, rng = jax.random.split(rng)
                params = self._apply_weight_noise(params, nrng)
            if cd is not None:
                params = dtypes.cast_float_tree(params, cd)
                x = dtypes.cast_float_tree(x, cd)
            out, new_state, rnn_states = self._forward(
                params, state, x, train=True, rng=rng, mask=mask,
                rnn_init=rnn_init, pre_output_last=fused)
            if cd is not None and losses_mod.wants_f32_logits(loss_fn,
                                                              fused):
                out = out.astype(jnp.float32)
            kw = {"from_logits": True} if fused else {}
            loss = loss_fn(y, out, mask=lmask, **kw)
            return loss + self._reg_score(master), (new_state, rnn_states)

        def step(params, opt_state, state, rnn_init, x, y, mask, lmask,
                 rng):
            (loss, (new_state, rnn_states)), grads = jax.value_and_grad(
                loss_with_state, has_aux=True)(
                    params, state, rnn_init, x, y, mask, lmask, rng)
            # stop state gradients across segment boundary (truncation)
            rnn_states = jax.tree.map(jax.lax.stop_gradient, rnn_states)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            params = self._apply_constraints(params)
            return params, opt_state, new_state, rnn_states, loss

        return sentry.jit(step, name="MultiLayerNetwork.tbptt_step")

    # ------------------------------------------------------------------
    # inference
    # ------------------------------------------------------------------
    def _make_output_fn(self):
        cd = self.conf.compute_dtype

        def infer(params, state, x, mask):
            if cd is not None:
                params = dtypes.cast_float_tree(params, cd)
                state = dtypes.cast_float_tree(state, cd)
                x = dtypes.cast_float_tree(x, cd)
            out, _, _ = self._forward(params, state, x, train=False,
                                      rng=None, mask=mask)
            return out.astype(jnp.float32) if cd is not None else out

        return sentry.jit(infer, name="MultiLayerNetwork.output")

    def output(self, x, train: bool = False, mask=None):
        """Reference: MultiLayerNetwork.output (SURVEY §3.3)."""
        x = jnp.asarray(np.asarray(x))
        self._refresh_ambient_trace()
        if self._output_fn is None:
            self._output_fn = self._make_output_fn()
        return self._output_fn(self.params, self.state, x, mask)

    def warmup(self, specs):
        """AOT-compile the train step, scanned loop, and output fn for
        every declared shape bucket BEFORE the first batch/request (see
        ``perf.warmup``): ``.lower().compile()`` from abstract shapes —
        no real data, no device stall at first use. Returns
        ``{"compiled": n, "seconds": t}``."""
        from deeplearning4j_tpu.perf.warmup import warmup_network
        self._refresh_ambient_trace()
        return warmup_network(self, specs)

    def feed_forward(self, x, train: bool = False):
        """All layer activations (reference feedForward): list, input
        first."""
        x = jnp.asarray(np.asarray(x))
        params = self._materialize_ties(self.params)
        acts = [x]
        cur = x
        for i, layer in enumerate(self.layers):
            proc = self.conf.input_preprocessors.get(i)
            if proc is not None:
                cur = proc.pre_process(cur)
            cur, _ = layer.apply(params[_lname(i)],
                                 self.state[_lname(i)], cur,
                                 train=train, rng=None)
            acts.append(cur)
        return acts

    def activate_selected_layers(self, from_: int, to: int, x):
        cur = jnp.asarray(np.asarray(x))
        params = self._materialize_ties(self.params)
        for i in range(from_, to + 1):
            proc = self.conf.input_preprocessors.get(i)
            if proc is not None:
                cur = proc.pre_process(cur)
            cur, _ = self.layers[i].apply(
                params[_lname(i)], self.state[_lname(i)], cur,
                train=False, rng=None)
        return cur

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    def rnn_time_step(self, x, mask=None):
        """Stateful single/multi-step inference (reference rnnTimeStep):
        carries recurrent state between calls."""
        x = jnp.asarray(np.asarray(x))
        squeeze = x.ndim == 2
        if squeeze:
            x = x[:, None, :]
        out, _, rnn_states = self._forward(
            self.params, self.state, x, train=False, rng=None, mask=mask,
            rnn_init=self._rnn_state)
        self._rnn_state = rnn_states
        if squeeze and out.ndim == 3:
            out = out[:, -1]
        return out

    # ------------------------------------------------------------------
    # scoring / evaluation
    # ------------------------------------------------------------------
    def score(self, dataset=None) -> float:
        if dataset is None:
            return self.score_
        x, y = dataset.features, dataset.labels
        loss_name, fused = self._last_loss()
        out, _, _ = self._forward(
            self.params, self.state, jnp.asarray(np.asarray(x)),
            train=False, rng=None,
            mask=getattr(dataset, "features_mask", None),
            pre_output_last=fused)
        kw = {"from_logits": True} if fused else {}
        loss = losses_mod.get(loss_name)(
            jnp.asarray(np.asarray(y)), out,
            mask=getattr(dataset, "labels_mask", None), **kw)
        return float(loss + self._reg_score(self.params))

    def evaluate(self, iterator):
        """Classification evaluation (reference MultiLayerNetwork
        .evaluate(DataSetIterator) → Evaluation)."""
        from deeplearning4j_tpu.eval_.evaluation import Evaluation
        e = Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            if hasattr(ds, "features"):
                x, y = ds.features, ds.labels
            else:
                x, y = ds
            out = self.output(x)
            e.eval(np.asarray(y), np.asarray(out))
        return e

    def evaluate_regression(self, iterator):
        from deeplearning4j_tpu.eval_.evaluation import RegressionEvaluation
        e = RegressionEvaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            x, y = (ds.features, ds.labels) if hasattr(ds, "features") \
                else ds
            e.eval(np.asarray(y), np.asarray(self.output(x)))
        return e

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def num_params(self) -> int:
        return sum(int(np.prod(np.shape(l)))
                   for l in jax.tree.leaves(self.params))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    def summary(self) -> str:
        lines = ["=" * 68,
                 f"{'Layer':<30}{'Output':<20}{'Params':>10}",
                 "=" * 68]
        total = 0
        for i, layer in enumerate(self.layers):
            n = sum(int(np.prod(np.shape(l)))
                    for l in jax.tree.leaves(self.params[_lname(i)]))
            total += n
            lines.append(f"{type(layer).__name__:<30}"
                         f"{str(self._layer_shapes[i]):<20}{n:>10,}")
        lines.append("=" * 68)
        lines.append(f"Total params: {total:,}")
        return "\n".join(lines)

    def clone(self) -> "MultiLayerNetwork":
        import copy
        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        net.params = jax.tree.map(lambda x: x, self.params)
        net.state = jax.tree.map(lambda x: x, self.state)
        net._input_shape = getattr(self, "_input_shape", None)
        net._build_optimizer()
        return net

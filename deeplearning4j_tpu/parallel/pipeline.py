"""Pipeline parallelism (PP) — GPipe-style microbatch pipelining over a
mesh axis.

NEW capability beyond the reference (SURVEY §2.5 marks PP "NO" —
deeplearning4j never splits a model across devices by depth).

TPU-native design: the S pipeline stages live on S devices along a
``stage`` mesh axis (stage-stacked params, ``PartitionSpec("stage",
…)``); inside ``shard_map`` each device runs its stage and hands its
activation to the next device with ``lax.ppermute`` over ICI — the
classic bubble schedule: with M microbatches the loop runs M+S-1 ticks,
utilization M/(M+S-1). The whole schedule is ONE ``lax.scan`` inside
ONE jitted program: no host round-trips between microbatches, and
``jax.grad`` differentiates straight through the ppermutes (reverse
pipeline runs automatically in the backward pass)."""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, *,
                   mesh: Mesh, axis: str = "stage"):
    """Run microbatches through the stage pipeline.

    stage_fn(params_for_one_stage, x[mb, ...]) -> y[mb, ...] with the
    SAME activation shape for every stage (residual-block style).
    stage_params: pytree whose leaves are stacked [S, ...].
    x_micro: [M, mb, ...] microbatches.
    Returns y_micro [M, mb, ...] — outputs of the LAST stage in input
    order.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    T = M + S - 1                       # schedule length (bubble incl.)

    def per_device(params_stacked, xm):
        # shard_map gives each device its own [1, ...] params slice
        params = jax.tree.map(lambda p: p[0], params_stacked)
        idx = lax.axis_index(axis)
        is_first = idx == 0
        is_last = idx == S - 1
        fwd_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            held, outbuf = carry
            # stage 0 ingests microbatch t (zeros after the stream ends)
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = xm[mb_idx]
            x_in = jnp.where(is_first, fresh, held)
            y = stage_fn(params, x_in)
            # last stage writes tick t's result to slot t-(S-1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            write = jnp.logical_and(is_last, t >= S - 1)
            outbuf = lax.cond(
                write,
                lambda b: lax.dynamic_update_index_in_dim(
                    b, y, out_idx, 0),
                lambda b: b, outbuf)
            # rotate activations one stage forward over ICI
            held_next = lax.ppermute(y, axis, fwd_perm)
            return (held_next, outbuf), None

        zero = jnp.zeros_like(xm[0])
        outbuf0 = jnp.zeros_like(xm)
        (_, outbuf), _ = lax.scan(tick, (zero, outbuf0),
                                  jnp.arange(T))
        # non-last stages contribute zeros; psum selects the last
        # stage's buffer without a host gather
        return lax.psum(jnp.where(is_last, outbuf, 0.0), axis)

    return shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stage_params),
                  P()),
        out_specs=P(),
        check_vma=False)(stage_params, x_micro)


def make_mlp_stage(activation=jax.nn.relu):
    """A simple residual MLP stage for stacked params {"W": [S,d,d],
    "b": [S,d]} — the shape-preserving stage_fn pipeline_apply needs."""
    def stage_fn(params, x):
        return x + activation(x @ params["W"] + params["b"])
    return stage_fn


def pipeline_train_step(stage_fn: Callable, loss_fn: Callable, *,
                        mesh: Mesh, axis: str = "stage",
                        optimizer=None):
    """Builds a jitted (params, opt_state, x_micro, y_micro) ->
    (params, opt_state, loss) step: forward pipeline, loss on last
    stage's outputs, backward pipeline via jax.grad, optimizer update.
    """
    import optax
    opt = optimizer or optax.sgd(1e-2)

    def total_loss(params, x_micro, y_micro):
        out = pipeline_apply(stage_fn, params, x_micro, mesh=mesh,
                             axis=axis)
        return loss_fn(out, y_micro)

    @jax.jit
    def step(params, opt_state, x_micro, y_micro):
        loss, g = jax.value_and_grad(total_loss)(params, x_micro,
                                                 y_micro)
        updates, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step, opt

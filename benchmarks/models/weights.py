"""Weights made by the benchmark, on the device, in one jitted call.

The plain references may take nothing the program has made, so the
benchmark makes every weight itself from ``--seed`` and hands the same
tree to the program (``net.params``) and, later, to the reference. The
program contributes only the tree's names and shapes.
"""
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number a little over 2**31 (the
    driver's seeds are large): the low 31 bits seed the key, the rest
    is folded in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def shapes_of(tree):
    """Names and shapes of a parameter tree, without its arrays."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def init_traced(init):
    """The zoo model's own ``init()`` traced into ONE program: the net
    it returns, and the shapes of its parameters.

    Run as a user runs it, ``init()`` draws every weight leaf by leaf,
    each through small programs of its own, and the benchmark then
    throws the draw away for its own weights: 3 s of a warm set-up for
    ResNet-50, the phase that swung most with the host's load (2.8 to
    3.6 s; my chip run 16, PR 23). Traced, the draw is dead code; the
    layers' state and the optimizer's come out as the program makes
    them, and ``net.params`` is left for :func:`weight_maker`."""
    found = {}

    def program():
        net = found["net"] = init()
        found["shapes"] = shapes_of(net.params)
        return net.state, net.opt_state

    state, opt_state = jax.jit(program)()
    net = found["net"]
    net.params, net.state, net.opt_state = None, state, opt_state
    return net, found["shapes"]


def weight_maker(shapes, seed: int, init_of):
    """A function that makes every leaf of ``shapes`` from the seed in
    one jitted call, the same tree each time it is called (it is traced
    and loaded once: the second call costs the draw alone).

    ``init_of(path, shape)`` gives ``("normal", std)`` or
    ``("const", value)``. ``path`` is the tuple of dict keys down to
    the leaf."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    plan = []
    for i, (path, sds) in enumerate(flat):
        names = tuple(getattr(k, "key", str(k)) for k in path)
        plan.append((i, sds.shape, sds.dtype, init_of(names, sds.shape)))

    def make(key):
        out = []
        for i, shape, dtype, (kind, value) in plan:
            if kind == "const":
                out.append(jnp.full(shape, value, dtype))
            else:
                out.append((jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                    * value).astype(dtype))
        return out

    made, key = jax.jit(make), seed_key(seed)
    return lambda: jax.tree_util.tree_unflatten(treedef, made(key))


def fan_in_std(shape, gain: float = 1.0) -> float:
    """sqrt(gain / fan_in): every axis but the last feeds one output."""
    return math.sqrt(gain / math.prod(shape[:-1]))

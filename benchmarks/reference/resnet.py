"""Plain reference of ResNet-50 training (He et al. 2015, arXiv
1512.03385): forward in training mode, softmax cross-entropy, gradients
and the Nesterov-momentum update, in straightforward float32
``jax.numpy``/``lax`` under ``jax.default_matmul_precision("highest")``.

Imports nothing of the program. It reads a parameter tree by the zoo's
names (``stem_conv``, ``res3_1_b_bn``, ``fc``), which the benchmark
made from the seed. Departures from the paper, all following the zoo
model this configuration names: the stride of a down-sampling
bottleneck sits on its first 1x1 convolution (as in the paper; later
"v1.5" variants move it to the 3x3), every convolution pads ``SAME``,
batch-norm epsilon is 1e-5.

``precision="fp8"`` is the benchmark's control (see PERF.md): what the
configuration holds in bfloat16, the weights and every layer's output,
is rounded to float8 e4m3 with a per-tensor scale, forward and
backward, sums still in float32: the step a later PR might be tempted
by. It has to come out as not correct.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _round_f8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(x.dtype) * scale


@jax.custom_vjp
def fake_f8(x):
    """Round to scaled float8 e4m3; the cotangent is rounded too."""
    return _round_f8(x)


fake_f8.defvjp(lambda x: (_round_f8(x), None),
               lambda _, g: (_round_f8(g),))


def _operand(x, precision):
    return fake_f8(x) if precision == "fp8" else x


def conv(x, w, stride, precision):
    return lax.conv_general_dilated(
        _operand(x, precision), _operand(w, precision),
        window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, p):
    """Training mode: statistics of this batch (two-pass variance)."""
    mu = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mu), axis=(0, 1, 2))
    return (x - mu) * lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]


def conv_bn(p, name, x, stride, relu, precision):
    y = _operand(conv(x, p[f"{name}_conv"]["W"], stride, precision),
                 precision)
    y = batch_norm(y, p[f"{name}_bn"])
    return _operand(jax.nn.relu(y) if relu else y, precision)


def stem(p, x, precision):
    h = conv_bn(p, "stem", x, 2, True, precision)
    return lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "SAME")


def bottleneck(p, x, stride, downsample, precision):
    y = conv_bn(p, "a", x, stride, True, precision)
    y = conv_bn(p, "b", y, 1, True, precision)
    y = conv_bn(p, "c", y, 1, False, precision)
    if downsample:
        x = conv_bn(p, "sc", x, stride, False, precision)
    return _operand(jax.nn.relu(x + y), precision)


def head_loss(p, h, y, precision):
    """Mean softmax cross-entropy over the batch, from the last
    stage's activations: global average pool, classifier, loss."""
    logits = (_operand(jnp.mean(h, axis=(1, 2)), precision)
              @ _operand(p["W"], precision) + p["b"])
    return -jnp.mean(jnp.sum(y * jax.nn.log_softmax(logits), axis=-1))


# Layer by layer: every piece is a small program of its own, so the
# sixteen bottlenecks share eight compilations, a batch of 256 fits
# (only the pieces' inputs are kept; a piece's backward pass computes
# its forward again), and the compiler's work stays in seconds.
@functools.partial(jax.jit, static_argnames=("fn", "static"))
def _forward(fn, static, p, x):
    return fn(p, x, *static)


@functools.partial(jax.jit, static_argnames=("fn", "static"))
def _backward(fn, static, p, x, g):
    _, vjp = jax.vjp(lambda p, x: fn(p, x, *static), p, x)
    return vjp(g)


def _pieces(params, stage_blocks, precision):
    """``(function, static arguments, parameters, names)`` of each
    piece from the image to the last stage, in order."""
    out = [(stem, (precision,),
            {"stem_conv": params["stem_conv"],
             "stem_bn": params["stem_bn"]}, None)]
    for s, blocks in enumerate(stage_blocks):
        for i in range(blocks):
            prefix = f"res{s + 2}_{i}_"
            p = {k[len(prefix):]: v for k, v in params.items()
                 if k.startswith(prefix) and v}
            out.append((bottleneck,
                        (2 if (i == 0 and s > 0) else 1, i == 0,
                         precision), p, prefix))
    return out


def loss_and_grads(params, x, y, stage_blocks, precision="float32"):
    """One batch: the loss and its gradient for every parameter."""
    pieces = _pieces(params, tuple(stage_blocks), precision)
    inputs, h = [], x
    for fn, static, p, _ in pieces:
        inputs.append(h)
        h = _forward(fn, static, p, h)
    loss, (g_fc, g) = jax.jit(
        jax.value_and_grad(head_loss, argnums=(0, 1)),
        static_argnames="precision")(params["fc"], h, y,
                                     precision=precision)
    grads = {"fc": g_fc}
    for (fn, static, p, prefix), h_in in zip(reversed(pieces),
                                             reversed(inputs)):
        g_p, g = _backward(fn, static, p, h_in, g)
        grads.update(g_p if prefix is None else
                     {prefix + k: v for k, v in g_p.items()})
    return loss, grads


def train_steps(params, batches, config, precision="float32"):
    """Follow the first ``len(batches)`` Nesterov steps from ``params``.

    Returns each step's loss, the momentum trace after the last step
    (``g + momentum * trace``: the gradients as the optimizer holds
    them) and the parameters after it."""
    lr = config["training"]["learning_rate"]
    mu = config["training"]["momentum"]

    @jax.jit
    def update(params, trace, g):
        trace = jax.tree.map(lambda g, t: g + mu * t, g, trace)
        params = jax.tree.map(lambda p, g, t: p - lr * (g + mu * t),
                              params, g, trace)
        return params, trace

    params = {k: v for k, v in params.items() if v}  # layers with weights
    trace = jax.tree.map(jnp.zeros_like, params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for x, y in batches:
            loss, g = loss_and_grads(
                params, jnp.asarray(x, jnp.float32),
                jnp.asarray(y, jnp.float32), config["stage_blocks"],
                precision)
            params, trace = update(params, trace, g)
            losses.append(float(loss))
    return losses, trace, params

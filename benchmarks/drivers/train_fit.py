"""Training driver: a configuration through the public
``ComputationGraph.fit(iterator, steps_per_loop=k)``.

Set-up builds ONE net, drives it through its first group of ``k`` steps
by the same call and feed the window uses (that call compiles), keeps
what those steps left behind for the comparison, warms one whole call,
and hands the same net to the window. The window times whole ``fit``
calls, each ending in the blocking fetch of its last group's losses,
until ``--seconds`` have passed. The plain reference follows the same
first ``k`` steps after the window, when the net is freed.

Workload keys: ``driver_params.steps_per_loop``; ``traffic.params`` as
the generator's, with ``batches_per_call``.
"""
import gc
import time

import numpy as np

#: seconds at the window's end that a ``--trace 1`` run traces
TRACE_TAIL_S = 3.0


class LossLog:
    """A listener as ``fit`` calls it: the loss of every step."""

    def __init__(self):
        self.losses = []

    def iteration_done(self, net, iteration, epoch):
        self.losses.append(float(net.score_))

    def on_epoch_start(self, net):
        pass

    def on_epoch_end(self, net):
        pass


def momentum_traces(opt_state) -> dict:
    """The optimizer's momentum trace per layer, from its state."""
    found = {}

    def walk(node):
        if hasattr(node, "_fields") and hasattr(node, "trace"):
            for layer, leaves in node.trace.items():
                if isinstance(leaves, dict) and leaves:
                    found[layer] = leaves
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (tuple, list)):
            for v in node:
                walk(v)

    walk(opt_state)
    return found


def leaf_norms(tree, minus=None) -> dict:
    """``{"layer/leaf": norm}`` of a two-level tree (less another)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        if b is not None:
            a = jax.tree.map(lambda x, y: x.astype(jnp.float32)
                             - y.astype(jnp.float32), a, b)
        return jax.tree.map(
            lambda x: jnp.linalg.norm(x.astype(jnp.float32).ravel()), a)

    def with_weights(t):        # a pooling layer has none
        return t and {k: v for k, v in t.items() if v}

    out = jax.device_get(norms(with_weights(tree), with_weights(minus)))
    return {f"{layer}/{leaf}": float(v)
            for layer, leaves in out.items() for leaf, v in leaves.items()}


def worst_leaf_gap(got: dict, want: dict):
    """The largest gap between the program's norm and the reference's
    over the leaves, against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but
    zero). Returns ``(gap, leaf)``."""
    floor = float(np.median(list(want.values())))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}
    # a norm that is not a number is the widest gap there is
    leaf = max(gaps, key=lambda k: np.inf if np.isnan(gaps[k]) else gaps[k])
    return gaps[leaf], leaf


def numbers(losses, trace, params, start) -> dict:
    """What the first group of steps left behind, as compared: each
    step's loss, each leaf's norm of the momentum trace and of the
    parameters' change."""
    return {"losses": [float(v) for v in losses],
            "trace": leaf_norms(trace),
            "change": leaf_norms(params, minus=start)}


def reference_numbers(ctx, batches, start, precision="float32") -> dict:
    """The plain reference (or, in a lower precision, the control)
    following the same steps from the same weights."""
    ref = ctx.plugin("reference", ctx.config["reference"])
    losses, trace, params = ref.train_steps(start, batches, ctx.config,
                                            precision)
    return numbers(losses, trace, params, start)


def compare(got: dict, want: dict) -> dict:
    """The three numbers ``correct`` rests on, each with the leaf or
    step that gave it."""
    loss = [abs(a - b) / abs(b)
            for a, b in zip(got["losses"], want["losses"])]
    trace_gap, trace_leaf = worst_leaf_gap(got["trace"], want["trace"])
    change_gap, change_leaf = worst_leaf_gap(got["change"],
                                             want["change"])
    return {"loss_gap": max(loss), "grad_trace_gap": trace_gap,
            "param_change_gap": change_gap,
            "at": {"loss_step": loss.index(max(loss)),
                   "trace_leaf": trace_leaf, "change_leaf": change_leaf}}


class Trainer:
    """One net with its feed: what set-up builds and the window uses."""

    def __init__(self, ctx):
        cfg, wl = ctx.config, ctx.workload
        self.ctx, self.tp = ctx, wl["traffic"]["params"]
        self.k = wl["driver_params"]["steps_per_loop"]
        self.gen = ctx.plugin("traffic", wl["traffic"]["generator"])
        built = ctx.plugin("models", cfg["builder"]).build(
            cfg, ctx.seed, ctx.mark)
        self.net, self.remake = built["net"], built["remake"]
        ctx.mark("net built, weights made")
        self.pool = self.gen.make_pool(self.tp, cfg, ctx.seed)
        ctx.mark("host batches made")
        self.log = LossLog()
        self.net.listeners.append(self.log)
        self.at = 0

    def fit_call(self, n_batches):
        """One public ``fit`` over the next ``n_batches`` of the pool;
        it returns after the fetch of its last group's losses."""
        it = self.gen.CycledBatches(self.pool, n_batches, self.at,
                                    self.ctx.annotate)
        self.at += n_batches
        with self.ctx.annotate("fit-call"):
            t0 = time.perf_counter()
            self.net.fit(it, steps_per_loop=self.k)
            return t0, time.perf_counter()

    def first_group(self) -> dict:
        """The first ``k`` steps, through the window's call and feed."""
        self.fit_call(self.k)
        self.ctx.mark("first group of steps made")
        start = self.remake()
        return numbers(self.log.losses[:self.k],
                       momentum_traces(self.net.opt_state),
                       self.net.params, start)

    def free(self):
        """Drop the program's state; the first group's batches stay."""
        batches = self.pool[:self.k]
        self.net.params = self.net.opt_state = self.net.state = None
        self.net = self.pool = None
        gc.collect()
        return batches


def readings(ctx) -> dict:
    """For ``tools/read_limits.py``: what the limits are set from. The
    sound program, the float8 control and a reference that leaves an
    eighth of every batch out, each against the plain reference."""
    t = Trainer(ctx)
    got = t.first_group()
    batches = t.free()
    want = reference_numbers(ctx, batches, t.remake())
    control = reference_numbers(ctx, batches, t.remake(), "fp8")
    cut = [(x[:-(len(x) // 8)], y[:-(len(y) // 8)]) for x, y in batches]
    partial = reference_numbers(ctx, cut, t.remake())
    return {"program": compare(got, want),
            "control_fp8": compare(control, want),
            "fault_partial_batch": compare(partial, want)}


def set_up(ctx):
    """All of set-up: the trainer the window uses, and what its first
    group of steps left behind."""
    t = Trainer(ctx)
    got = t.first_group()           # compiles; the compared steps
    ctx.mark("first group's numbers read")
    t.fit_call(t.tp["batches_per_call"])    # one whole call, warm
    ctx.mark("warm call done; the window opens")
    return t, got


def run(ctx) -> dict:
    from deeplearning4j_tpu.perf import sentry

    t, got = set_up(ctx)
    k, per_call = t.k, t.tp["batches_per_call"]
    traces_before = sentry.total_traces()
    compile_report = ctx.compile_report()

    # -- the window ------------------------------------------------------
    spans = []
    t_open = time.perf_counter()
    tracing = False
    while True:
        now = time.perf_counter() - t_open
        if now >= ctx.seconds:
            break
        if ctx.trace and not tracing and now >= ctx.seconds - TRACE_TAIL_S:
            ctx.start_trace(host_spans=False)   # fit stages big arrays
            tracing = True
        t0, t1 = t.fit_call(per_call)
        spans.append([t0, t1, per_call * t.tp["batch"]])
    if tracing:
        ctx.stop_trace()
    window_losses = t.log.losses[k + per_call:]
    retraces = sentry.total_traces() - traces_before
    peak = ctx.memory_peak_bytes()

    # -- the plain reference, with the program's state freed -------------
    batches = t.free()
    t_ref = time.perf_counter()
    want = reference_numbers(ctx, batches, t.remake())
    ctx.log(f"reference followed {k} steps in "
            f"{time.perf_counter() - t_ref:.1f} s")
    gaps = compare(got, want)
    ctx.log(f"losses program={got['losses']} reference={want['losses']}")
    ctx.log(f"compared at {gaps['at']}")
    limits = ctx.config["correct"]
    checks = [ctx.check(name, gaps[name], limits[name]["limit"])
              for name in ("loss_gap", "grad_trace_gap",
                           "param_change_gap")]
    checks += [
        # the window's run: the loss falls, and nothing traces in it
        ctx.check("window_loss_ratio",
                  float(np.mean(window_losses[-k:])) / got["losses"][0],
                  limits["window_loss_ratio"]["limit"]),
        ctx.check("traces_in_window", retraces, 0),
    ]
    return {
        "attempted": len(spans), "failed": 0, "checks": checks,
        "setup_end": t_open, "window": [t_open, spans[-1][1]],
        "spans": {"fit-call": spans},
        "steps_per_call": per_call, "steps_per_program": k,
        "batch": t.tp["batch"],
        # no host spans in this trace: every call is one ``fit-call``
        "idle_span": "fit-call",
        "compile_report": compile_report,
        "memory_peak_bytes": peak,
    }

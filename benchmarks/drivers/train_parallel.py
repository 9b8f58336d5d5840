"""Data-parallel training driver: a configuration through the public
``ParallelWrapper.fit(iterator)`` in SYNC mode, one program across the
cell's chips, the global batch split over them.

``train_fit.py`` with the call swapped: set-up builds ONE net and its
wrapper, drives them through the first ``compared_steps`` steps by the
window's own call and feed (that call compiles), keeps what those
steps left behind, warms one whole call and hands the same wrapper to
the window; the window times whole ``fit`` calls (every step of the
wrapper ends in the blocking fetch of its loss). The comparison is
``train_fit``'s own, number for number, against limits of the cell's
own: the workload file's ``correct`` block, read on four chips at the
global batch (a gradient's norm shrinks with the batch, bf16's rounding
does not, so the limits read at batch 256 do not carry over).

The plain reference follows the same steps after the window at the
GLOBAL batch: the wrapper's SYNC step is one program over the global
batch (``parallel/wrapper.py::_build_sync_step``: a ``jit`` with the
batch sharded over ``data``, not a per-replica ``shard_map``), so its
batch norms normalise over all 1,024 rows and its loss is their mean.
The reference is given the same batches as arrays laid over the same
chips, a quarter of the rows each, and the same weights on every chip;
its plain ``jax.numpy`` then computes over the whole batch, each chip
holding what it would hold of a batch of 256.

Workload keys: ``chips``; ``driver_params.compared_steps``;
``traffic.params`` as the generator's, with ``batches_per_call``;
``correct`` (a limit each for ``loss_gap``, ``grad_trace_gap``,
``param_change_gap`` and ``window_loss_ratio``).
"""
import gc
import time

import numpy as np

from benchmarks.drivers import train_fit

#: seconds at the window's end that a ``--trace 1`` run traces
TRACE_TAIL_S = train_fit.TRACE_TAIL_S


class HostBatches:
    """The pool's next ``count`` batches as the wrapper's iterator
    wants them: objects with ``features`` and ``labels``."""

    def __init__(self, batches):
        self._batches = batches

    def __iter__(self):
        from deeplearning4j_tpu.data.dataset import DataSet
        for x, y in self._batches:
            yield DataSet(x, y)


class Trainer:
    """One net behind one ``ParallelWrapper``, with its feed."""

    def __init__(self, ctx):
        import jax
        from deeplearning4j_tpu.parallel import ParallelWrapper

        cfg, wl = ctx.config, ctx.workload
        self.ctx, self.tp = ctx, wl["traffic"]["params"]
        self.k = wl["driver_params"]["compared_steps"]
        self.chips = wl["chips"]
        if len(jax.devices()) < self.chips:
            raise SystemExit(f"the cell needs {self.chips} chip(s); JAX "
                             f"found {len(jax.devices())}")
        self.gen = ctx.plugin("traffic", wl["traffic"]["generator"])
        built = ctx.plugin("models", cfg["builder"]).build(
            cfg, ctx.seed, ctx.mark)
        self.net, self.remake = built["net"], built["remake"]
        ctx.mark("net built, weights made")
        self.wrapper = ParallelWrapper(self.net, workers=self.chips,
                                       mode=ParallelWrapper.SYNC)
        self.pool = self.gen.make_pool(self.tp, cfg, ctx.seed)
        ctx.mark("host batches made")
        self.log = train_fit.LossLog()
        self.net.listeners.append(self.log)
        self.at = 0

    def fit_call(self, n_batches):
        """One public ``ParallelWrapper.fit`` over the next
        ``n_batches`` of the pool; every step of it has fetched its
        loss when it returns."""
        it = HostBatches(self.gen.CycledBatches(
            self.pool, n_batches, self.at, self.ctx.annotate))
        self.at += n_batches
        with self.ctx.annotate("fit-call"):
            t0 = time.perf_counter()
            self.wrapper.fit(it)
            return t0, time.perf_counter()

    def first_group(self) -> dict:
        """The first ``k`` steps, through the window's call and feed."""
        self.fit_call(self.k)
        self.ctx.mark("first group of steps made")
        return train_fit.numbers(
            self.log.losses[:self.k],
            train_fit.momentum_traces(self.net.opt_state),
            self.net.params, self.remake())

    def free(self):
        """Drop the program's state; the first group's batches stay."""
        batches = self.pool[:self.k]
        self.net.params = self.net.opt_state = self.net.state = None
        self.net = self.wrapper = self.pool = None
        gc.collect()
        return batches


def over_chips(chips: int, batches, start):
    """The reference's inputs laid over the cell's chips: each batch
    split by rows, the weights whole on every chip."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("data",))
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    return ([(jax.device_put(x, rows), jax.device_put(y, rows))
             for x, y in batches],
            jax.tree.map(lambda a: jax.device_put(a, whole), start))


def reference_numbers(ctx, t, batches, precision="float32") -> dict:
    batches, start = over_chips(t.chips, batches, t.remake())
    return train_fit.reference_numbers(ctx, batches, start, precision)


def readings(ctx) -> dict:
    """For ``tools/read_limits.py``: the sound program, the float8
    control and a reference that leaves an eighth of every batch out,
    each against the plain reference at the global batch. Each reading
    is logged as it is made: a control compiles for minutes on a cold
    cache, and a call cut short keeps what it had read."""
    t = Trainer(ctx)
    got = t.first_group()
    batches = t.free()
    want = reference_numbers(ctx, t, batches)
    out = {}

    def read(name, numbers):
        out[name] = train_fit.compare(numbers, want)
        ctx.log(f"reading seed={ctx.seed} {name}: {out[name]}")

    read("program", got)
    read("control_fp8", reference_numbers(ctx, t, batches, "fp8"))
    cut = [(x[:-(len(x) // 8)], y[:-(len(y) // 8)]) for x, y in batches]
    read("fault_partial_batch", reference_numbers(ctx, t, cut))
    return out


def run(ctx) -> dict:
    from deeplearning4j_tpu.perf import sentry

    t = Trainer(ctx)
    got = t.first_group()           # compiles; the compared steps
    ctx.mark("first group's numbers read")
    k, per_call = t.k, t.tp["batches_per_call"]
    t.fit_call(per_call)            # one whole call, warm
    ctx.mark("warm call done; the window opens")
    traces_before = sentry.total_traces()
    compile_report = ctx.compile_report()

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

    batches = t.free()
    t_ref = time.perf_counter()
    want = reference_numbers(ctx, t, batches)
    ctx.log(f"reference followed {k} steps over {t.chips} chip(s) in "
            f"{time.perf_counter() - t_ref:.1f} s")
    gaps = train_fit.compare(got, want)
    ctx.log(f"losses program={got['losses']} reference={want['losses']}")
    ctx.log(f"compared at {gaps['at']}")
    limits = ctx.workload["correct"]
    checks = [ctx.check(name, gaps[name], limits[name]["limit"])
              for name in ("loss_gap", "grad_trace_gap",
                           "param_change_gap")]
    checks += [
        ctx.check("window_loss_ratio",
                  float(np.mean(window_losses[-k:])) / got["losses"][0],
                  limits["window_loss_ratio"]["limit"]),
        ctx.check("traces_in_window", retraces, 0),
    ]
    return {
        "attempted": len(spans), "failed": 0, "checks": checks,
        "setup_end": t_open, "window": [t_open, spans[-1][1]],
        "spans": {"fit-call": spans},
        "steps_per_call": per_call, "steps_per_program": 1,
        # a chip's share of the global batch: what one device's
        # program trains (the MFU reader counts programs by device)
        "batch": t.tp["batch"] // t.chips,
        "idle_span": "fit-call",
        "compile_report": compile_report,
        "memory_peak_bytes": peak,
    }

"""Benchmark — ResNet-50 training throughput (images/sec/chip), the
headline metric of the README's "Targets" table (target #2).

One process that needs the chip: no TPU attached is an error, a device
kind whose peaks are unknown is an error, and any failure exits
non-zero with its traceback. Prints ONE JSON line: {"metric", "value",
"unit", "vs_baseline", "device", ...}; every number in it was measured
in this process on the device it names.

Protocol: steady-state throughput — warmup (compile + 20 steps)
excluded, median of 3 timed runs of 40 steps, each ended by
``jax.block_until_ready`` on the updated parameters (JAX returns before
the device finishes; on a directly attached chip ``block_until_ready``
is the barrier). Synthetic ImageNet-shaped data (224x224x3, 1000
classes) resident on the device, so storage never bounds the number.
Whole-graph jitted train step, bf16 compute / fp32 master params (the
reference's cuDNN path is fp32 with per-op JNI dispatch — SURVEY §3.2).

``vs_baseline``: the reference publishes no numbers (README "Targets").
Denominator: 2500 images/sec — A100-class ResNet-50 fp16 training
throughput (NGC/MLPerf-era single-GPU ballpark), the "match nd4j-cuda
on A100" bar from BASELINE.json's north star.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

A100_CLASS_RESNET50_IMAGES_PER_SEC = 2500.0

METRIC = "resnet50_train_images_per_sec_per_chip"

BATCH, SIZE, K_INNER = 256, 224, 4
WARMUP_STEPS, TIMED_STEPS = 20, 40


def attached_tpu():
    """The attached device as JAX reports it; raises unless it is a
    TPU whose peaks ``environment.DEVICE_PEAKS`` knows."""
    import jax
    from deeplearning4j_tpu import environment
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py needs a TPU, JAX found "
                         f"{dev.platform!r} — a CPU number is never "
                         "written under a device metric")
    environment.device_peaks()      # LookupError for an unknown kind
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp

    device = attached_tpu()

    from deeplearning4j_tpu.zoo import ResNet50
    from deeplearning4j_tpu.nn import updaters as upd

    batch, size, k_inner = BATCH, SIZE, K_INNER
    net = ResNet50(num_classes=1000, seed=123,
                   input_shape=(size, size, 3),
                   updater=upd.Nesterovs(learning_rate=0.1, momentum=0.9),
                   compute_dtype="bfloat16").init()

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, size, size, 3)),
                    jnp.float32)
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])

    # scanned device loop (steps_per_loop): K train steps per dispatched
    # executable — the idiomatic TPU training loop (host/dispatch latency
    # amortised; the reference instead pays a JNI crossing PER OP).
    assert WARMUP_STEPS % k_inner == 0 and TIMED_STEPS % k_inner == 0
    loop = net._make_train_loop()
    params, opt_state, state = net.params, net.opt_state, net.state
    base = jax.random.PRNGKey(0)
    x_stack = {"input": jnp.stack([x] * k_inner)}
    y_stack = [jnp.stack([y] * k_inner)]
    rngs = jnp.stack([jax.random.fold_in(base, i) for i in range(k_inner)])

    def run_steps(n_steps):
        nonlocal params, opt_state, state
        t0 = time.perf_counter()
        for _ in range(n_steps // k_inner):
            params, opt_state, state, _ = loop(
                params, opt_state, state, x_stack, y_stack, {}, {},
                rngs)
        jax.block_until_ready(params)
        return time.perf_counter() - t0

    run_steps(WARMUP_STEPS)             # compile + warm steps, untimed
    runs = sorted(TIMED_STEPS * batch / run_steps(TIMED_STEPS)
                  for _ in range(3))
    images_per_sec = runs[1]            # median of 3

    # compile subsystem (perf/): wall-time XLA spent compiling this
    # run's entry points and whether the persistent cache paid for any
    # of it — a second bench run against a warm DL4J_TPU_COMPILE_CACHE
    # should show persistent_hits == compile_requests
    from deeplearning4j_tpu.perf import compile_report
    compile_rec = compile_report()

    # telemetry spine (obs/): the instrumentation rides every step, so
    # its tracing-OFF cost must be provably negligible — measured here
    # against this run's real step time (acceptance: < 1%)
    from deeplearning4j_tpu import obs
    obs_rec = obs.overhead_report(step_seconds=batch / images_per_sec)
    obs_rec["step_summary"] = obs.metrics.step_summary()

    # numerics observatory (obs/numerics.py): diagnostics-on vs -off
    # step time on this run's model — the in-step per-layer stats must
    # cost a small, measured fraction of the step (acceptance: <= 5%
    # on the smoke model), with scalars-only host traffic at cadence.
    # NB: reuses the live post-timing (params, opt_state, state) — the
    # scanned loop donated net's original buffers.
    numerics_rec = obs.numerics.measure_diag_overhead(
        net, params, opt_state, state, ({"input": x}, [y], {}, {}),
        jax.random.fold_in(jax.random.PRNGKey(0), 0),
        k=4)

    # fleet observability plane (obs/fleet.py): publish-cadence cost
    # against this run's real step — the off path (no plane) must be
    # ~0 (one branch, the PR 2 bar) and the on path < 1% of step time
    # at the default 1 Hz cadence
    fleet_rec = obs.fleet.measure_publish_overhead(
        step_seconds=batch / images_per_sec)

    # device-time observatory (obs/devtime.py): the fit-loop hook's
    # off-path cost against this run's real step (DL4J_TPU_DEVTIME
    # unset must be one branch — the PR 2 bar), plus capture counters
    devtime_rec = obs.devtime.measure_capture_overhead(
        step_seconds=batch / images_per_sec)

    print(json.dumps({
        "metric": METRIC,
        "value": round(images_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(
            images_per_sec / A100_CLASS_RESNET50_IMAGES_PER_SEC, 3),
        # state device/batch/shape with every number
        "device": device,
        "batch": batch,
        "image_size": size,
        "compute_dtype": "bfloat16",
        "compile": compile_rec,
        "obs": obs_rec,
        "numerics": numerics_rec,
        "fleet_obs": fleet_rec,
        "devtime": devtime_rec,
    }), flush=True)


if __name__ == "__main__":
    main()

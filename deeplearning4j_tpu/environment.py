"""Runtime flags — the central environment-variable registry.

Reference: ``org.nd4j.config.ND4JEnvironmentVars`` /
``ND4JSystemProperties`` / ``DL4JSystemProperties`` — the reference's
tier-2 config system (SURVEY §5 "Config / flag system"): runtime
behavior toggles separate from model configs (tier 1, JSON beans) and
backend selection (tier 3, here JAX platform selection).

Every supported variable is declared here with type, default, and
purpose, and read through :func:`get_flag` so the full surface is
greppable and ``describe()`` prints the live values (the analog of the
reference's documented constants class).
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


def _bool(v: str) -> bool:
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclass(frozen=True)
class Flag:
    name: str
    default: Any
    parse: Callable[[str], Any]
    doc: str


FLAGS: Dict[str, Flag] = {}


def _register(name, default, parse, doc):
    FLAGS[name] = Flag(name, default, parse, doc)


# -- data / resources (reference ND4JSystemProperties resources dir) -------
_register("DL4J_TPU_DATA_DIR", os.path.expanduser("~/.dl4j_tpu/data"),
          str, "dataset fetcher cache root (MNIST/EMNIST/CIFAR/...)")
_register("DL4J_TPU_CRASH_DUMP_DIR", ".", str,
          "directory for HBM-OOM crash dumps (DL4JSystemProperties "
          "crash-dump location analog)")

# -- precision / execution (reference dtype + workspace debug props) -------
_register("DL4J_TPU_DEFAULT_DTYPE", "float32", str,
          "default NDArray float dtype (float32|bfloat16|float64)")
_register("DL4J_TPU_VERBOSE_OPS", False, _bool,
          "print every op execution (libnd4j verbose mode analog)")
_register("DL4J_TPU_PROFILING", False, _bool,
          "enable OpProfiler aggregation from startup")

# -- distributed bring-up (reference parameter-server/Spark env) -----------
_register("DL4J_TPU_COORD", None, str,
          "jax.distributed coordinator address host:port")
_register("DL4J_TPU_NPROC", None, int,
          "number of processes in the multi-host job")
_register("DL4J_TPU_PROC_ID", None, int,
          "this process's rank in the multi-host job")

# -- kernels ---------------------------------------------------------------
_register("DL4J_TPU_FLASH_MIN_T", 1024, int,
          "key-sequence length at/above which scaled_dot_attention "
          "dispatches to the Pallas flash kernel on TPU (crossover "
          "measured on v5e, tools/flash_crossover.py)")
_register("DL4J_TPU_KERNEL_FORCE", False, _bool,
          "force every gated fused-kernel dispatch site "
          "(scaled_dot_attention flash, ops/fused_norms.py norm "
          "epilogues) onto the Pallas kernel path regardless of "
          "platform/size gates — interpret mode on CPU, so CI can "
          "exercise the dispatch decision itself; semantic refusals "
          "(float64, causal Tq>Tk, shard_map-on-CPU) still fall back")
_register("DL4J_TPU_FUSED_NORM_MIN_F", 256, int,
          "trailing feature dim at/above which the norm epilogues "
          "(ops/fused_norms.py) dispatch to the fused Pallas kernels "
          "on TPU — below it the row pads to a full 128-lane block "
          "for no bandwidth win")

# -- compile subsystem (perf/: persistent XLA cache + retrace sentry) ------
#: fixed in-checkout default of the persistent compile cache: the
#: path is part of what makes a later run hit, so it holds no
#: temporary name, pid or time (.gitignore lists it)
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
_register("DL4J_TPU_COMPILE_CACHE", DEFAULT_COMPILE_CACHE, str,
          "persistent XLA compilation cache dir shared across "
          "processes/restarts ('' | '0' | 'off' | 'none' disables; "
          "default <checkout>/.jax_cache, skipped only when "
          "JAX_PLATFORMS names the CPU alone). A set "
          "JAX_COMPILATION_CACHE_DIR wins over this flag and over "
          "DL4J_TPU_COMPILE_STORE")
_register("DL4J_TPU_COMPILE_CACHE_MIN_BYTES", -1, int,
          "min serialized-executable size eligible for the persistent "
          "cache (-1: cache everything)")
_register("DL4J_TPU_COMPILE_CACHE_MIN_SECS", 0.0, float,
          "min compile wall-time eligible for the persistent cache "
          "(0: cache everything)")
_register("DL4J_TPU_COMPILE_STORE", "", str,
          "content-addressed compile store root "
          "(perf/compile_store.py): fleet-shared compiled artifacts "
          "fenced by (store version, jaxlib, topology); when set it "
          "supersedes DL4J_TPU_COMPILE_CACHE — its fenced xla/ plane "
          "becomes the JAX persistent-cache dir ('' | '0' | 'off' "
          "disables; explicit opt-in, so it applies on CPU too)")
_register("DL4J_TPU_RETRACE_BUDGET", 16, int,
          "distinct UNPLANNED traced shapes tolerated per jitted entry "
          "point before the retrace sentry warns (warmed-up shapes "
          "don't count against it)")
_register("DL4J_TPU_RETRACE_STRICT", False, _bool,
          "retrace sentry raises RetraceBudgetExceeded instead of "
          "warning when a function blows its retrace budget")

# -- telemetry spine (obs/: span tracer + metrics + worker health) ---------
_register("DL4J_TPU_TRACE", "", str,
          "record export (obs/trace.py; the record ring itself is "
          "always on): '' none; '1' writes Chrome-trace JSONL to "
          "dl4j_tpu_trace_<pid>.jsonl; any other value is the output "
          "path (drop the file into chrome://tracing/Perfetto)")
_register("DL4J_TPU_TRACE_RING", 65536, int,
          "size of the always-on record ring, in records of about "
          "100 B: half a minute of a saturated gateway (some 5 records "
          "a 33 ms iteration, one a request) after a set-up's few "
          "thousand compile records; readers and crash dumps take its "
          "tail, and a reader refuses a window it has overwritten")
_register("DL4J_TPU_METRICS_PORT", 0, int,
          "serve Prometheus /metrics + /healthz on this port from "
          "startup (0: don't autostart; obs.metrics.start_server() "
          "starts it on demand, port 0 -> ephemeral)")
_register("DL4J_TPU_STALE_WORKER_SECS", 30.0, float,
          "heartbeat age beyond which /healthz flags a worker stale")

# -- resilience (resilience/: fault injection + hardened recovery) ---------
_register("DL4J_TPU_FAULT_PLAN", "", str,
          "deterministic fault-injection plan (resilience/faults.py): "
          "'' off (one-branch zero-overhead path); a named plan "
          "(ckpt-io-flake, worker-crash, etl-flake, serving-crash, "
          "preempt) or 'site:error=OSError:p=0.5:seed=3;...' rule "
          "syntax — see docs/OPS.md failure & recovery runbook")

# -- elastic fleets (resilience/elastic.py) --------------------------------
_register("DL4J_TPU_HOST_LEASE_SECS", 15.0, float,
          "membership lease window: a host whose lease file is older "
          "than this is evicted from the fleet at the next agreement "
          "round; the collective watchdog defaults to 2x this window")
_register("DL4J_TPU_ELASTIC_DIR", None, str,
          "shared directory for the elastic membership coordinator "
          "(leases, proposals, committed mesh-epoch record); unset = "
          "elastic layer off")
_register("DL4J_TPU_HOST_ID", None, str,
          "this host's stable identity in the elastic fleet (lease "
          "file name, deterministic leader ordering)")
_register("DL4J_TPU_ELASTIC_PORT_BASE", 31300, int,
          "base port for generation-salted coordination services: "
          "mesh epoch g binds base+(g mod 1000) so a stale generation "
          "can never capture the new generation's workers")

# -- device-time observatory (obs/devtime.py) ------------------------------
_register("DL4J_TPU_DEVTIME", "", str,
          "device-time observatory (obs/devtime.py): '' off (the fit "
          "loops pay one branch); truthy installs the cadence monitor "
          "— every DL4J_TPU_DEVTIME_EVERY-th step opens a short "
          "jax.profiler.trace window, attributes device time to the "
          "named_scope'd layers, and publishes dl4j_tpu_devtime_* "
          "gauges + the hot-path gap report")
_register("DL4J_TPU_DEVTIME_EVERY", 100, int,
          "capture-window cadence in fit iterations (the capture "
          "costs ~a profiler session + an xplane parse — keep sparse)")
_register("DL4J_TPU_DEVTIME_STEPS", 3, int,
          "fit steps each capture window stays open for")
_register("DL4J_TPU_PEAK_TFLOPS", None, float,
          "explicit override of the roofline compute peak in TFLOP/s "
          "— the denominator of devtime's per-scope utilization "
          "(unset: the attached device_kind's entry in "
          "environment.DEVICE_PEAKS; an unknown kind is an error)")
_register("DL4J_TPU_PEAK_HBM_GBS", None, float,
          "explicit override of the roofline memory peak in GB/s "
          "(unset: DEVICE_PEAKS by device_kind)")

# -- communication observatory (obs/commtime.py) ---------------------------
_register("DL4J_TPU_COMMTIME", "", str,
          "communication observatory (obs/commtime.py): '' off (the "
          "fit loops pay one branch); truthy installs the cadence "
          "monitor — every DL4J_TPU_COMMTIME_EVERY-th step opens a "
          "short jax.profiler.trace window, attributes collective "
          "device time + static HLO wire bytes to the named_scope'd "
          "phases, and publishes dl4j_tpu_comm_* gauges")
_register("DL4J_TPU_COMMTIME_EVERY", 100, int,
          "comm capture-window cadence in fit iterations")
_register("DL4J_TPU_COMMTIME_STEPS", 3, int,
          "fit steps each comm capture window stays open for")
_register("DL4J_TPU_PEAK_ICI_GBS", None, float,
          "explicit override of the interconnect roofline peak in "
          "GB/s per link direction — the denominator of commtime's "
          "link utilization (unset: DEVICE_PEAKS by device_kind; "
          "CPU/gloo captures are estimate-only)")

# -- elastic serving fleet (serving/fleet.py) ------------------------------
_register("DL4J_TPU_FLEET_SHED_BUDGET", 8, int,
          "max in-flight streams the serving router may structurally "
          "shed per replica eviction (each surfaced as "
          "SequenceAborted); beyond it the router keeps re-routing "
          "instead of aborting")

# -- fleet observability plane (obs/fleet.py) ------------------------------
_register("DL4J_TPU_FLEET_PUBLISH_SECS", 1.0, float,
          "telemetry-snapshot publish cadence: each elastic host "
          "atomically writes <elastic_dir>/telemetry/<host>.json at "
          "most this often (the fleet aggregator's sampling floor)")
_register("DL4J_TPU_FLEET_RING", 50, int,
          "flight-recorder ring size: last-N step records dumped as "
          "the postmortem bundle when a run dies")
_register("DL4J_TPU_FLEET_TELEMETRY", True, _bool,
          "fleet observability plane for elastic training: '0' "
          "disables snapshot publishing + the flight recorder "
          "(non-elastic training never pays more than one branch "
          "either way)")

# -- UI / examples ---------------------------------------------------------
_register("DL4J_TPU_UI_PORT", 9000, int,
          "training dashboard HTTP port (DL4JSystemProperties UI port)")
_register("DL4J_TPU_EXAMPLE_FAST", False, _bool,
          "examples run in seconds-scale FAST mode (CI smoke)")


#: per-chip peaks the rooflines divide by, keyed by
#: ``jax.devices()[0].device_kind`` — ONE table, with its source. A
#: kind that is not here is an error (:func:`device_peaks`), never a
#: default: a utilization against another chip's peak is a wrong
#: number. The ``DL4J_TPU_PEAK_*`` flags are explicit overrides.
DEVICE_PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "tflops": 197.0,        # bf16 MXU
        "hbm_gbs": 819.0,
        "ici_gbs": 45.0,        # per link, per direction
        "source": "Google Cloud documentation, 'TPU v5e': 197 bf16 "
                  "TFLOP/s, 819 GB/s HBM; ICI 45 GB/s per link per "
                  "direction: jax-ml.github.io/scaling-book",
    },
}

PEAK_FLAGS = {"tflops": "DL4J_TPU_PEAK_TFLOPS",
               "hbm_gbs": "DL4J_TPU_PEAK_HBM_GBS",
               "ici_gbs": "DL4J_TPU_PEAK_ICI_GBS"}


def device_peaks(*keys: str) -> Dict[str, float]:
    """Peaks for the attached device: each of ``keys`` (``tflops``,
    ``hbm_gbs``, ``ici_gbs``) from its explicit ``DL4J_TPU_PEAK_*``
    override when set, else from :data:`DEVICE_PEAKS` by
    ``device_kind``. Raises ``LookupError`` for a kind the table does
    not know — looked up only for keys without an override, so a
    process that names all its peaks never touches a backend."""
    out: Dict[str, float] = {}
    for key in keys or tuple(PEAK_FLAGS):
        val = get_flag(PEAK_FLAGS[key])
        if val is None:
            import jax
            kind = jax.devices()[0].device_kind
            if kind not in DEVICE_PEAKS:
                raise LookupError(
                    f"no published peaks for device kind {kind!r} "
                    f"(known: {sorted(DEVICE_PEAKS)}): add it to "
                    "environment.DEVICE_PEAKS with its source, or set "
                    f"{PEAK_FLAGS[key]} explicitly")
            val = DEVICE_PEAKS[kind][key]
        out[key] = float(val)
    return out


def get_flag(name: str) -> Any:
    """Read a declared flag from the environment (typed, defaulted)."""
    flag = FLAGS[name]
    raw = os.environ.get(name)
    if raw is None:
        return flag.default
    return flag.parse(raw)


def describe() -> str:
    """Live flag table (the documented-constants-class analog)."""
    lines = [f"{'variable':<28} {'value':<24} purpose"]
    for name, flag in sorted(FLAGS.items()):
        val = get_flag(name)
        lines.append(f"{name:<28} {str(val):<24} {flag.doc}")
    return "\n".join(lines)


def apply_startup_flags() -> None:
    """Apply flags that configure global singletons (called lazily from
    package __init__; safe to call repeatedly)."""
    from deeplearning4j_tpu.utils.profiler import OpProfiler
    prof = OpProfiler.get_instance()
    if get_flag("DL4J_TPU_VERBOSE_OPS"):
        prof.enable_verbose_mode(True)
    if get_flag("DL4J_TPU_PROFILING"):
        prof.enabled = True
    # telemetry spine: gate on the raw env so an idle process never
    # pays the obs import
    if os.environ.get("DL4J_TPU_TRACE", "").strip():
        from deeplearning4j_tpu.obs import trace as obs_trace
        obs_trace.configure_from_env()
    if get_flag("DL4J_TPU_METRICS_PORT"):
        from deeplearning4j_tpu.obs import metrics as obs_metrics
        obs_metrics.start_server()
    # device-time observatory: the raw-env gate skips INSTALLING the
    # cadence monitor (the module itself rides the obs package
    # import) — unset leaves the fit-loop hooks on the one-branch
    # monitor-is-None path
    if os.environ.get("DL4J_TPU_DEVTIME", "").strip():
        from deeplearning4j_tpu.obs import devtime as obs_devtime
        obs_devtime.configure_from_env()
    # communication observatory: same raw-env gate — unset leaves the
    # fit-loop comm hooks on the one-branch monitor-is-None path
    if os.environ.get("DL4J_TPU_COMMTIME", "").strip():
        from deeplearning4j_tpu.obs import commtime as obs_commtime
        obs_commtime.configure_from_env()
    # fault injection: gate on the raw env so the unset path never
    # imports the resilience package at startup
    if os.environ.get("DL4J_TPU_FAULT_PLAN", "").strip():
        from deeplearning4j_tpu.resilience import faults
        faults.configure_from_env()

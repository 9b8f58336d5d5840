"""A share of the device's peak: what the algorithm needs, from the
shape functions in ``benchmarks/trace/shapes.py``, over what the trace
says it took and the peak in ``benchmarks/trace/peaks.py``.

``args``: ``kind`` one of

- ``step_flops``: FLOPs the samples trained in the traced window
  require (``ops_fn`` a sample, from the configuration) over the device
  time of the programs matching ``module`` and the bf16 peak;
- ``decode_bytes``: weight and cache bytes a decode step must read
  (``obs["decode_kv_tokens_per_step"]`` cached positions on average)
  over the mean device time of the programs matching ``module`` and
  the memory bandwidth.
"""
from benchmarks.trace import shapes, xplane
from benchmarks.trace.peaks import peaks


def read(obs: dict, args: dict):
    trace = obs.get("trace")
    if not trace or not trace["devices"]:
        return None
    peak = peaks(obs["device"]["kind"])
    config = obs["config"]
    if args["kind"] == "step_flops":
        durs = xplane.module_durations(trace, args["module"])
        if not durs:
            return None
        samples = len(durs) * obs["steps_per_program"] * obs["batch"]
        flops = samples * getattr(shapes, args["ops_fn"])(config)
        return 100.0 * flops / (sum(durs) * peak["bf16_flops_per_s"])
    if args["kind"] == "decode_bytes":
        durs = xplane.module_durations(trace, args["module"])
        if not durs or "decode_kv_tokens_per_step" not in obs:
            return None
        need = (shapes.lm_decode_weight_bytes(config)
                + obs["decode_kv_tokens_per_step"]
                * shapes.lm_kv_bytes_per_token(config))
        return 100.0 * need / (sum(durs) / len(durs)
                               * peak["hbm_bytes_per_s"])
    raise ValueError(f"unknown kind {args['kind']!r}")

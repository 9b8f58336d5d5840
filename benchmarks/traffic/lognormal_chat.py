"""Serving traffic: open-loop conversation requests.

Parameters (the workload file's ``traffic.params``): ``rate_per_s``,
``prompt`` and ``output`` (each ``median``, ``sigma``, ``min``,
``max`` of a clipped log-normal), ``tenants``, ``arrivals``.

A window holds ``round(rate_per_s * seconds)`` requests. Their prompt
and output lengths are the evenly spaced quantiles of the two
log-normals, each in a balanced order (a Kronecker sequence: any
stretch of the window holds the whole range in its proportions), the
same for every seed: drawn afresh from each seed the lengths changed
the work of a window (``serve_tokens_per_s`` differed by 3% between
seeds that each repeated to 0.5%; my chip runs 6 and 8, PR 23). The
seed draws the token ids (and, in the driver, the weights) and, with
``"arrivals": "poisson"``, the times the requests are due.

``arrivals``:

- ``"poisson"``: a Poisson process at ``rate_per_s`` drawn from the
  seed, given its count: that many independent uniform times over the
  window, sorted. Bursts and lulls come as they come to a server, other
  ones with every seed, so a queue forms and empties below the knee.
- ``"quantiles"``: the evenly spaced quantiles of the process's
  exponential gaps in a balanced order, the same times for every seed
  and no bursts. For cells above the knee only, where the queue never
  empties and the times of arrival do not reach the scheduler.
"""
import math
from statistics import NormalDist

import numpy as np

#: irrational steps of the three Kronecker sequences
STEPS = {"prompt": 0.6180339887498949,      # golden ratio - 1
         "output": 0.41421356237309515,     # root 2 - 1
         "gap": 0.7320508075688772}         # root 3 - 1


def _lognormal_quantiles(spec: dict, n: int):
    inv = NormalDist().inv_cdf
    vals = [spec["median"] * math.exp(spec["sigma"] * inv((i + 0.5) / n))
            for i in range(n)]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def _balanced(sorted_values, step: float):
    """The values in the order of ``frac(j * step)``: consecutive ones
    lie far apart in rank, and every stretch covers the range evenly."""
    n = len(sorted_values)
    ranks = np.argsort(np.argsort(np.modf(np.arange(n) * step)[0]))
    return np.asarray(sorted_values)[ranks]


def generate(params: dict, seed: int, seconds: float, vocab_size: int):
    """Requests of one window: dicts of ``due_s`` (from the window's
    start), ``prompt`` (int32 ids), ``max_new``, ``tenant``, sorted by
    ``due_s``; all are due inside the window."""
    n = max(1, round(params["rate_per_s"] * seconds))
    prompts = _balanced(_lognormal_quantiles(params["prompt"], n),
                        STEPS["prompt"])
    outputs = _balanced(_lognormal_quantiles(params["output"], n),
                        STEPS["output"])
    if params["arrivals"] == "poisson":
        # a stream of its own, so the token ids do not depend on it
        due = np.sort(np.random.default_rng([seed, 1]).uniform(
            0.0, seconds, n))
    elif params["arrivals"] == "quantiles":
        due = np.cumsum(_balanced(
            [-math.log(1.0 - (i + 0.5) / n) for i in range(n)],
            STEPS["gap"]))
        due *= seconds * n / (n + 1) / due[-1]   # the last one inside too
    else:
        raise ValueError(f"unknown arrivals {params['arrivals']!r}")
    rng = np.random.default_rng(seed)
    tenants = params["tenants"]
    return [{"due_s": float(due[i]),
             "prompt": rng.integers(0, vocab_size, int(prompts[i]),
                                    dtype=np.int32),
             "max_new": int(outputs[i]),
             "tenant": tenants[i % len(tenants)]}
            for i in range(n)]

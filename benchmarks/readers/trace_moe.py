"""Roofline shares and the expert load of a latent-attention,
mixture-of-experts decode step: the bytes and operations its steps
NEED, counted here from the configuration
(``benchmarks/trace/shapes_latent_moe.py``) and from what the program
says its steps did, over what the device trace says the work took and
the peaks in ``benchmarks/trace/peaks.py``.

The program contributes counts on the ``serving.decode_step`` records
of the traced tail: ``active`` live slots, ``latent_rows`` cached
positions the step's attention reads, ``experts_hit`` held experts
with at least one pair (summed over the expert layers),
``expert_pairs`` the pairs they computed and ``expert_pairs_max`` the
fullest expert's (summed over the layers). They are checked against
the configuration: a step cannot hit more experts than are held, nor
compute more pairs than its rows route, nor read more rows than its
slots can hold; a traced run in which one does fails here.

Device time of a part of the step is the time of the device ops
named ``op`` (a pattern on ``xplane.label``) that ran inside a program
matching ``module``: the TPU's trace names ops by their HLO
instruction and carries no scope, so the experts' part is read as the
step's ``while`` ops (the loops over tiles of sorted pairs: the step
has no other loop; the sort before them and the gather after are left
out, a few per cent) and the latent attention's as the kernel's
custom calls (``latent_decode_attention``).

A program without the counts or the scopes (a parent commit) gives
``None``.

``args``: ``kind`` one of

- ``step``: fixed weights + experts hit x one expert's bytes + latent
  rows x a row's bytes, a step, over the mean device time of the
  programs matching ``module``;
- ``experts``: experts hit x one expert's bytes over the device time a
  step spends in the ops named ``op``;
- ``latent``: the larger of (latent rows x a row's bytes / bandwidth)
  and (latent rows x a row's operations / peak) over the device time a
  step spends in the ops named ``op``;
- ``load``: ``expert_pairs_max`` over the mean pairs of a held expert.
"""
import re

from benchmarks.trace import shapes_latent_moe as shapes
from benchmarks.trace import timeline, xplane
from benchmarks.trace.peaks import peaks

COUNTS = ("latent_rows", "expert_pairs", "experts_hit",
          "expert_pairs_max")


def tail_counts(obs: dict):
    """Means of the counts over the decode steps recorded in the
    traced tail that read a step launched before them (``ahead``: the
    expert counts are of the step a call READ), or ``None``."""
    records = timeline.window_records(obs)
    if records is None or "trace_window_s" not in obs:
        return None
    cfg = obs["config"]
    end = obs["window"][1]
    layers, held = shapes.expert_layers(cfg), cfg["n_routed_experts"]
    rows = []
    for r in records:
        if (r.name != "serving.decode_step" or not r.counts
                or any(k not in r.counts for k in COUNTS)
                or not r.counts.get("ahead")
                or not end - obs["trace_window_s"] <= r.stamps[0] <= end):
            continue
        c = r.counts
        if (c["experts_hit"] > layers * held
                or c["experts_hit"] > c["expert_pairs"]
                or c["expert_pairs_max"] > c["expert_pairs"]
                or c["expert_pairs"] > obs["max_slots"] * layers
                * min(cfg["num_experts_per_tok"], held)
                or c["latent_rows"] > c["active"]
                * cfg["assumed"]["max_len"]):
            raise ValueError(
                f"a step's counts {dict(c)} do not fit the "
                f"configuration: {layers} expert layers of {held} held "
                f"experts, {cfg['num_experts_per_tok']} a token")
        rows.append(c)
    if not rows:
        return None
    return {k: sum(c[k] for c in rows) / len(rows) for k in COUNTS}


def op_seconds(trace: dict, op: str, module: str):
    """Seconds the devices ran ops whose label matches ``op`` inside
    programs matching ``module``, and the number of those programs;
    ``None`` where the trace has no such op."""
    op_rx, mod_rx = re.compile(op), re.compile(module)
    spent, programs = 0.0, 0
    for dev in trace["devices"]:
        inside = xplane.merged((s, s + d) for name, s, d in dev["modules"]
                               if mod_rx.search(name))
        programs += len(inside)
        i = 0
        for name, s, d in sorted(dev["ops"], key=lambda o: o[1]):
            if d <= 0 or not op_rx.search(xplane.label(name)):
                continue
            while i < len(inside) and inside[i][1] <= s:
                i += 1
            if i < len(inside) and inside[i][0] <= s:
                spent += d / 1e9
    return (spent, programs) if spent and programs else None


def read(obs: dict, args: dict):
    trace = obs.get("trace")
    if not trace or not trace["devices"]:
        return None
    counts = tail_counts(obs)
    if not counts:
        return None
    cfg = obs["config"]
    if args["kind"] == "load":
        if not counts["expert_pairs"]:
            return None
        return counts["expert_pairs_max"] / (
            counts["expert_pairs"] / cfg["n_routed_experts"])
    peak = peaks(obs["device"]["kind"])
    bandwidth = peak["hbm_bytes_per_s"]
    hit_bytes = counts["experts_hit"] * shapes.expert_bytes(cfg)
    row_bytes = counts["latent_rows"] * shapes.latent_row_bytes(cfg)
    if args["kind"] == "step":
        steps = xplane.module_durations(trace, args["module"])
        if not steps:
            return None
        need = shapes.decode_fixed_weight_bytes(cfg) + hit_bytes + row_bytes
        return 100.0 * need / (sum(steps) / len(steps) * bandwidth)
    found = op_seconds(trace, args["op"], args["module"])
    if found is None:
        return None
    a_step = found[0] / found[1]
    if args["kind"] == "experts":
        return 100.0 * hit_bytes / bandwidth / a_step
    if args["kind"] == "latent":
        least = max(row_bytes / bandwidth,
                    counts["latent_rows"] * shapes.latent_row_flops(cfg)
                    / peak["bf16_flops_per_s"])
        return 100.0 * least / a_step
    raise ValueError(f"unknown kind {args['kind']!r}")

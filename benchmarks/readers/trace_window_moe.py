"""Roofline shares and counts of a windowed mixture-of-experts
decoder's decode step: the bytes it must read, counted from the
configuration's shapes (``benchmarks/trace/shapes_window_moe.py``) and
the program's own counts on its ``serving.decode_step`` records, over
what the device trace says the work took, BY SCOPE
(``devtime.joined_events`` through ``readers/trace_scope.py``: the same
work whether a kernel or plain XLA does it, and however many loops a
step holds), and the memory bandwidth in ``benchmarks/trace/peaks.py``.

The program contributes, a step of the traced tail: its live slots
(``active``), the pages ONE full layer's walk reads (``kv_pages``) and
ONE window layer's (``kv_pages_window``), the cached positions all
walks must read (``kv_rows_read``) and would read without a window
(``kv_rows_unwindowed``), the experts hit over all layers
(``experts_hit``) with the pairs computed (``expert_pairs``) and the
fullest experts' (``expert_pairs_max``). A program without the counts,
the scopes or the join (a parent commit) gives ``None``.

``args``: ``kind`` one of

- ``step``: fixed weights + experts hit + positions in range, over the
  mean device time of the programs matching ``module``;
- ``experts``: the hit experts' bytes over the device self time a step
  spent under ``scope``;
- ``walk``: the bytes of the pages a step's walks of ``layers``
  (``full`` or ``window``) must read (``block`` positions a page) over
  the self time under ``scope``;
- ``saved``: positions not read over positions an unwindowed walk
  would read, %;
- ``load``: the fullest expert's pairs over the mean expert's.
"""
from benchmarks.readers import trace_scope
from benchmarks.trace import shapes_window_moe as shapes
from benchmarks.trace import timeline, xplane
from benchmarks.trace.peaks import peaks

COUNTS = ("active", "kv_pages", "kv_pages_window", "kv_rows_read",
          "kv_rows_unwindowed", "experts_hit", "expert_pairs",
          "expert_pairs_max")


def tail_counts(obs: dict):
    """Means of the program's counts over the decode steps recorded in
    the traced tail of the window that READ a step (``experts_hit``
    above zero), or ``None`` where no record holds them all."""
    records = timeline.window_records(obs)
    if records is None or "trace_window_s" not in obs:
        return None
    end = obs["window"][1]
    cfg = obs["config"]
    n_layers = cfg["num_hidden_layers"]
    held = n_layers * cfg["moe_num_primary_experts"]
    rows = []
    for r in records:
        if (r.name != "serving.decode_step" or not r.counts
                or any(k not in r.counts for k in COUNTS)
                or not r.counts["experts_hit"]
                or not end - obs["trace_window_s"] <= r.stamps[0] <= end):
            continue
        c = r.counts
        if (c["experts_hit"] > held or c["kv_rows_read"]
                > c["kv_rows_unwindowed"] or c["expert_pairs"]
                > c["active"] * n_layers
                * cfg["moe_num_active_primary_experts"] + held):
            raise ValueError(f"the program's counts {c} do not fit the "
                             "configuration")
        rows.append(c)
    if not rows:
        return None
    return {k: sum(c[k] for c in rows) / len(rows) for k in COUNTS}


def read(obs: dict, args: dict):
    cfg = obs.get("config", {})
    if "sliding_window_layout" not in cfg:
        return None
    counts = tail_counts(obs)
    if not counts:
        return None
    kind = args["kind"]
    if kind == "saved":
        return 100.0 * (1.0 - counts["kv_rows_read"]
                        / counts["kv_rows_unwindowed"])
    if kind == "load":
        mean = counts["expert_pairs"] / cfg["moe_num_primary_experts"]
        return counts["expert_pairs_max"] / mean if mean else None
    trace = obs.get("trace")
    if not trace or not trace["devices"]:
        return None
    bandwidth = peaks(obs["device"]["kind"])["hbm_bytes_per_s"]
    if kind == "step":
        steps = xplane.module_durations(trace, args["module"])
        if not steps:
            return None
        need = shapes.decode_bytes(cfg, counts["experts_hit"],
                                   counts["kv_rows_read"])
        return 100.0 * need / (sum(steps) / len(steps) * bandwidth)
    ms = trace_scope.read(obs, {"kind": "ms", "per": "program",
                                "module": args["module"],
                                "scope": args["scope"]})
    if not ms:
        return None
    if kind == "experts":
        need = counts["experts_hit"] * shapes.expert_bytes(cfg)
    elif kind == "walk":
        window = args["layers"] == "window"
        pages = counts["kv_pages_window" if window else "kv_pages"]
        need = (shapes.layers_of(cfg, window) * pages * args["block"]
                * shapes.kv_bytes_per_row(cfg))
    else:
        raise ValueError(f"unknown kind {kind!r}")
    return 100.0 * need / (ms / 1e3 * bandwidth)

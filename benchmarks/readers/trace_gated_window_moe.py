"""Roofline shares and counts of a gated windowed mixture-of-experts
decoder's decode step (``benchmarks/trace/shapes_gated_window_moe.py``:
softmax layers of two kinds that differ in their head count, a leading
dense layer, a shared expert beside the routed ones):
``readers/trace_window_moe.py``'s quantities for the configurations
that name their layers by ``layer_types`` and ``mlp_layer_types``. The
bytes a step MUST read, from the configuration's shapes and the
program's own counts on its ``serving.decode_step`` records, over what
the device trace says the work took BY SCOPE
(``readers/trace_scope.py``) and the memory bandwidth in
``benchmarks/trace/peaks.py``.

The program contributes, a step of the traced tail, the counts
``trace_window_moe.COUNTS`` names. A program without the counts, the
scopes or the join (a parent commit) gives ``None``.

``args``: ``kind`` one of ``step``, ``experts``, ``saved``, ``load``
(as ``trace_window_moe``), and

- ``walk``: the bytes of the cached POSITIONS IN RANGE of the layers
  of ``layers`` (``full`` or ``window``), from ``kv_rows_read`` and
  ``kv_rows_unwindowed``, over the self time under ``scope``;
- ``hit``: the routed experts a step hit over those its sparse layers
  hold, %.
"""
from benchmarks.readers import trace_scope
from benchmarks.readers.trace_window_moe import COUNTS
from benchmarks.trace import shapes_gated_window_moe as shapes
from benchmarks.trace import timeline, xplane
from benchmarks.trace.peaks import peaks


def tail_counts(obs: dict):
    """Means of the program's counts over the decode steps recorded in
    the traced tail of the window that READ a step (``experts_hit``
    above zero), or ``None`` where no record holds them all."""
    records = timeline.window_records(obs)
    if records is None or "trace_window_s" not in obs:
        return None
    end = obs["window"][1]
    cfg = obs["config"]
    sparse = shapes.sparse_layers(cfg)
    held = sparse * cfg["num_experts"]
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
                > c["active"] * sparse * cfg["num_experts_per_tok"]
                + held):
            raise ValueError(f"the program's counts {c} do not fit the "
                             "configuration")
        rows.append(c)
    if not rows:
        return None
    return {k: sum(c[k] for c in rows) / len(rows) for k in COUNTS}


def read(obs: dict, args: dict):
    cfg = obs.get("config", {})
    if "mlp_layer_types" not in cfg or "layer_types" not in cfg:
        return None
    counts = tail_counts(obs)
    if not counts:
        return None
    kind = args["kind"]
    sparse = shapes.sparse_layers(cfg)
    if kind == "saved":
        return 100.0 * (1.0 - counts["kv_rows_read"]
                        / counts["kv_rows_unwindowed"])
    if kind == "load":
        mean = counts["expert_pairs"] / cfg["num_experts"]
        return counts["expert_pairs_max"] / mean if mean else None
    if kind == "hit":
        return 100.0 * counts["experts_hit"] / (sparse * cfg["num_experts"])
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
        # positions in range by kind, from the two totals: every layer
        # would read ``unwindowed / layers`` of them, a full layer does
        n_full = shapes.layers_of(cfg, False)
        full = n_full * counts["kv_rows_unwindowed"] / cfg[
            "num_hidden_layers"]
        rows = (counts["kv_rows_read"] - full
                if args["layers"] == "window" else full)
        need = rows * shapes.kv_bytes_per_row(cfg)
    else:
        raise ValueError(f"unknown kind {kind!r}")
    return 100.0 * need / (ms / 1e3 * bandwidth)

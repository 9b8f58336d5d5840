"""A ratio of two of the program's own counts, each summed over the
records of one name that lie in the WINDOW (the always-on ring of
``deeplearning4j_tpu/obs/trace.py``; the ring and ``obs["window"]``
are on one clock). A program without the ring, or whose records lack
a count (a parent commit), gives ``None``.

``args``: ``record`` (the records' name), ``part`` (a count) and
``whole`` (counts whose PRODUCT a record contributes: a retention
admission computes ``bucket`` x ``chunks`` rows), ``quantity`` one of

- ``missing_share``: 100 x (1 - sum of ``part`` / sum of ``whole``), %:
  with ``part`` the prompt's tokens and ``whole`` the rows its
  admission computes, the share of those rows that carry no token.
"""
from benchmarks.trace import timeline


def read(obs: dict, args: dict):
    records = timeline.window_records(obs)
    if records is None:
        return None
    w0, w1 = obs["window"]
    keys = [args["part"], *args["whole"]]
    mine = [r.counts for r in records if r.name == args["record"]
            and r.stamps[0] >= w0 and r.stamps[-1] <= w1
            and r.counts and all(k in r.counts for k in keys)]
    part = sum(c[args["part"]] for c in mine)
    whole = 0
    for c in mine:
        rows = 1
        for k in args["whole"]:
            rows *= c[k]
        whole += rows
    if not whole:
        return None
    if args["quantity"] == "missing_share":
        return 100.0 * (1.0 - part / whole)
    raise ValueError(f"unknown quantity {args['quantity']!r}")

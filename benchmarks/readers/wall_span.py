"""Host-clock spans the benchmark records around its calls into the
program: ``obs["spans"][name]`` is a list of ``[start, end, units]``.

``args``: ``span``; ``mode`` one of ``seconds`` (the spans' total
length), ``units_per_s`` (all units over the whole window, the time
between calls included) and ``ms_per_step`` (span time a step of the
program, ``obs["steps_per_call"]`` steps to a span).
"""


def read(obs: dict, args: dict):
    spans = obs.get("spans", {}).get(args["span"])
    if not spans:
        return None
    total = sum(end - start for start, end, _ in spans)
    if args["mode"] == "seconds":
        return total
    if args["mode"] == "units_per_s":
        start, end = obs["window"]
        return sum(units for _, _, units in spans) / (end - start)
    if args["mode"] == "ms_per_step":
        return 1e3 * total / (len(spans) * obs["steps_per_call"])
    raise ValueError(f"unknown mode {args['mode']!r}")

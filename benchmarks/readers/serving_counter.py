"""The program's serving counters over the window
(``dl4j_tpu_serving_*``): ``obs["histograms"][name]`` is the growth of
a histogram's ``count`` and ``sum`` (seconds) across the window,
``obs["samples"][name]`` a gauge as the driver sampled it.

``args``: ``quantity`` ``mean_ms`` of ``histogram``, or
``mean_share_of`` (the mean of ``samples`` over ``obs[of]``, %).
"""


def read(obs: dict, args: dict):
    if args["quantity"] == "mean_ms":
        h = obs.get("histograms", {}).get(args["histogram"])
        if not h or not h["count"]:
            return None
        return 1e3 * h["sum"] / h["count"]
    if args["quantity"] == "mean_share_of":
        samples = obs.get("samples", {}).get(args["samples"])
        if not samples:
            return None
        return 100.0 * sum(samples) / len(samples) / obs[args["of"]]
    raise ValueError(f"unknown quantity {args['quantity']!r}")

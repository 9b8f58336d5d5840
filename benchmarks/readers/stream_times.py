"""Times of requests as their client saw them: ``obs["requests"]`` is
one row a request sent, times in ms counted from when it was DUE.

``args``: ``quantity`` one of ``ttft_due_ms``, ``gap_ms`` (a request's
mean gap between output tokens), ``queue_wait_ms``, ``prefill_ms``
(with ``percentile`` and ``over``: ``sent`` counts every request, one
with no first token at the window's length; ``admitted`` and
``completed`` count those that got that far) and ``tokens_per_s``
(output tokens delivered inside the window over its length).
"""
import numpy as np


def read(obs: dict, args: dict):
    rows = obs.get("requests")
    if not rows:
        return None
    if args["quantity"] == "tokens_per_s":
        start, end = obs["window"]
        return obs["tokens_in_window"] / (end - start)
    if args["over"] != "sent":
        rows = [r for r in rows if r[args["over"]]]
    values = [r[args["quantity"]] for r in rows if args["quantity"] in r]
    if not values:
        return None
    return float(np.percentile(values, args["percentile"]))

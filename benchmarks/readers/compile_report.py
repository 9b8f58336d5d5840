"""The program's compile counters at the end of set-up
(``perf.sentry.total_compile_time_s``, ``perf.compile_cache.cache_stats``).

``args``: ``quantity`` one of ``compile_s`` (seconds the program spent
tracing and compiling, AOT warm-up included) and ``hit_share`` (the
share of its compile requests served from the persistent cache, %).
"""


def read(obs: dict, args: dict):
    rep = obs.get("compile_report")
    if not rep:
        return None
    if args["quantity"] == "compile_s":
        return rep["compile_s"]
    if args["quantity"] == "hit_share":
        if not rep["requests"]:
            return None
        return 100.0 * rep["hits"] / rep["requests"]
    raise ValueError(f"unknown quantity {args['quantity']!r}")

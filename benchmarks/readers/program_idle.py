"""Device-idle time by program phase: the traced tail's idle seconds
(the complement of the first device's busy intervals) laid over the
program's own records through ``benchmarks/trace/timeline.py``, which
also prints the whole table and its clock check in every traced run.
``None`` without a device trace, without the ring, or where the clock
check fails.

``args``: ``top``, ``step``, ``module`` as ``timeline.join`` takes them;
``quantity`` one of

- ``named_share``: share of the idle seconds under a named phase of
  the program (not a top-level record's own time, not outside it), %;
- ``phase_to_launch_ms_per_step``: idle seconds from the start of a
  step's ``phase`` to the start of the device program that step
  dispatches, over the steps dispatched in the traced tail, ms. With
  ``phase`` the staging (``h2d``) this is the staging the device waits
  out: the host's ``h2d`` stamps time the enqueueing alone, the copy
  goes on under ``dispatch`` and ``sync`` until the program can start.
"""
from benchmarks.trace import timeline


def read(obs: dict, args: dict):
    joined = timeline.join(obs, args)
    if joined is None or not joined["idle_s"]:
        return None
    idle = joined["idle"]
    if args["quantity"] == "named_share":
        unnamed = sum(sec for label, sec in idle.items()
                      if label == timeline.OUTSIDE
                      or label.endswith(" (self)"))
        return 100.0 * (1.0 - unnamed / joined["idle_s"])
    if args["quantity"] == "phase_to_launch_ms_per_step":
        if not joined["steps"]:
            return None
        waits = [(timeline.phase_bounds(step, args["phase"])[0], launch)
                 for step, launch in joined["launches"]]
        waited = timeline.idle_between(joined["gaps"], waits)
        timeline.log("idle from %s to the program's start: %.6f s of "
                     "%.6f s idle, over %d steps" % (
                         args["phase"], waited, joined["idle_s"],
                         joined["steps"]))
        return 1e3 * waited / joined["steps"]
    raise ValueError(f"unknown quantity {args['quantity']!r}")

"""Instrumentation lint — the telemetry spine's CI fence (tier-1 via
``tests/test_lint_instrumentation.py``).

Twelve AST rules over ``deeplearning4j_tpu/``:

1. **Every ``sentry.jit``-wrapped hot path emits obs telemetry.** A
   module that builds jitted entry points with ``sentry.jit(...)`` is
   a hot path by definition; it must also call one of the obs
   emission APIs (``obs.record_step`` / ``record_etl`` /
   ``record_worker_step`` / ``span`` / ``trace.add_span``) so the
   timeline can attribute the wall time those entry points consume.
   Without this rule a future PR can add a jitted path whose cost is
   invisible to ``chrome://tracing`` and ``/metrics``.

2. **No ``time.time()`` for step timing outside ``obs/``.** The spine
   has ONE step clock — ``obs.now`` (``time.perf_counter``): mixing in
   wall clocks reintroduces exactly the disconnected-timing mess this
   layer replaced (non-monotonic under NTP slew, incomparable bases).
   Allowlisted: modules using wall time for *calendar* purposes
   (termination deadlines, record timestamps), never step timing.

3. **No host-side device reductions over params/grads in
   listener/stats paths.** Listener code (``train/stats.py``,
   ``train/listeners.py``) runs per recording interval on the host;
   building ``jnp``/``jax.tree.map`` reductions there re-dispatches
   a device program per layer per record AND pins full param trees
   between records (the old ``StatsListener._prev_params`` copy this
   rule fences out). Per-layer training health is computed IN-STEP
   by the numerics observatory — ``obs/numerics.py`` is the
   allowlisted home for these reductions (it lives outside the
   scanned listener set by construction); listeners consume its
   scalars. ``jax.tree.leaves`` + numpy stays legal (the explicit
   opt-in host histograms).

4. **Every ``ParallelWrapper`` step variant has a warmup feed.** The
   wrapper's ``warmup()`` iterates the module-level ``WARMUP_FEEDS``
   table; a ``_build_*_step`` method without a table entry is a step
   signature ``perf/warmup.py`` can never AOT-compile — its first
   real batch cold-traces and stalls the whole mesh. The rule keeps
   the builder set and the feed table in lockstep (both directions:
   no missing feeds, no stale feeds).

5. **Every fault-injection site is declared, live, and drillable.**
   ``resilience/faults.py`` failure modes only exist where a
   ``faults.inject("<site>")`` call is threaded through a real code
   path, and only stay honest while something exercises them. Three
   checks keep the site table and the codebase in lockstep: every
   literal ``inject`` site must appear in ``KNOWN_SITES`` (else the
   plan parser rejects plans that target it), every ``KNOWN_SITES``
   entry must have at least one call site (a dead site advertises a
   drill that cannot fire), and every injected site must be covered
   by a ``NAMED_PLANS`` rule or referenced from ``tests/`` (an
   unplanned, untested site rots silently as code moves — exactly how
   the elastic layer's ``host_death``/``coordinator`` sites would
   otherwise age out).

6. **Every metric family name is declared in the one FAMILIES
   table.** ``obs/metrics.py::FAMILIES`` is the single registry of
   ``dl4j_tpu_*`` family names (and kinds). Three checks kill
   stringly-typed family drift between producers and consumers:
   every emit site in the package (a ``REGISTRY.counter/gauge/
   histogram`` registration, a pull-time collector tuple, or a fleet
   ``AGGREGATE_FAMILIES`` entry) must name a declared family with the
   declared kind; every declared family must have an emit site (no
   dead declarations advertising metrics that never exist); and every
   ``dl4j_tpu_*`` token in ``tools/tpu_watch.py`` and ``docs/OPS.md``
   must resolve to a declared family (exactly, via a histogram
   ``_bucket``/``_sum``/``_count`` suffix, or as a prefix filter
   matching at least one family) — a dashboard or runbook can't watch
   a family the code stopped (or never started) emitting.

7. **Every jitted entry point in ``serving/`` is sentried and has a
   warmup feed.** The serving gateway's whole contract is zero
   retraces after ``warmup()`` — a raw ``jax.jit`` there bypasses the
   retrace sentry's accounting, and a sentried entry point outside a
   ``_build_*`` builder (or a builder without a ``WARMUP_FEEDS``
   entry) is a compile the warmup can never reach: the first live
   request pays it mid-traffic. Same shape as rule 4 (the
   ``ParallelWrapper`` feed-table rule): builders ⊆ feeds ⊆ builders,
   and ``warmup`` must actually read the table. And every entry point
   built in ``serving/scheduler.py``, like every ``*.train_loop`` in
   the package, says what it is (``sentry.jit(..., identity=...)``),
   so that a warm start loads it by a key that needs no trace.

8. **The device-time observatory's scope contract holds.** Per-layer
   device-time attribution (``obs/devtime.py``, ARCHITECTURE.md §16)
   only works while the annotation points stay annotated: the layer
   loops in ``nn/multilayer.py``/``nn/graph.py`` ``_forward`` (ONE
   site covers every registered layer type and every zoo model built
   from them), the hand-rolled zoo transformer's decode/prefill
   paths, the serving scheduler's paged decode step, and the ZeRO
   collective phases — each listed function must contain a
   ``devtime.scope``/``jax.named_scope`` call (:data:`SCOPE_SITES`).
   The ``dl4j_tpu_devtime_*`` family block must exist in the
   FAMILIES table (rule 6 already checks kinds — this catches the
   block being deleted outright), and every ``gap.<key>`` token
   ``docs/OPS.md``/``tools/tpu_watch.py`` reference must resolve
   against ``obs/devtime.py``'s ``GAP_KEYS`` tuple, so the runbook
   and dashboard can't drift from the gap-report schema.

9. **The fused-kernel library stays registered and honest.** Pallas
   kernels live in ``ops/`` ONLY (a raw ``pl.pallas_call`` anywhere
   else bypasses the dispatch-gate/fallback/parity contract of
   ARCHITECTURE §17), and every PUBLIC kernel — a non-underscore
   module-level function that reaches a ``pallas_call`` through
   private same-module helpers — must be declared in
   ``ops/kernel_registry.py`` ``KERNEL_REGISTRY`` with (a) a
   ``fallback`` naming a function that exists in its module (the
   value-identical XLA path the gate-off program runs), (b) a
   ``parity`` test reference that resolves to a real test
   (``tests/<file>.py::<test>``), and (c) a ``scope`` that the kernel
   function actually emits via ``devtime.scope`` AND that is listed in
   :data:`SCOPE_SITES` so rule 8 keeps enforcing it — the same
   table-driven fence that keeps rules 4/7/8 honest, in both
   directions (no unregistered kernels, no stale registry entries).

10. **The speculative-decode grid stays warmable and observable.**
    The serving scheduler's spec-decode entry points compile one
    executable per draft width ``k`` — if ``serving/scheduler.py``
    defines any ``_build_spec*`` builder it must also define the
    module-level ``SPEC_KS`` tuple literal (the supported k grid the
    constructor pins requests to), list the builder in
    ``WARMUP_FEEDS`` (rule 7's table), and ``warmup()`` must reference
    ``SPEC_KS`` so the warmed signatures and the admissible widths
    cannot drift apart (an off-grid k would cold-trace mid-traffic —
    exactly the stall the zero-retrace fence exists to prevent). On
    the consumer side every ``dl4j_tpu_serving_spec_*`` /
    ``dl4j_tpu_serving_prefix_*`` token in ``tools/tpu_watch.py`` and
    ``docs/OPS.md`` must resolve against the FAMILIES table, and each
    consumer must reference at least one ``dl4j_tpu_serving_spec_*``
    family — a spec-decode rollout whose accept rate no dashboard or
    runbook watches regresses silently.

11. **The communication observatory's attribution contract holds.**
    The wire ledger (``obs/commtime.py``, ARCHITECTURE.md §19) joins
    every collective to a ``dl4j.*`` scope — which only works while
    the modules that EMIT collectives explicitly keep their emitting
    phases scope-annotated. Every bare or ``jax.lax.*`` call to a
    collective primitive (``psum``/``pmean``/``psum_scatter``/
    ``all_gather``/``ppermute``/``all_to_all``/``pshuffle``) in
    :data:`COLLECTIVE_SCOPE_PATHS` (``parallel/zero.py``,
    ``parallel/composed.py``, ``parallel/compression.py``) must sit
    inside a function carrying a ``devtime.scope``/``named_scope``
    call — an unscoped collective lands in the ledger's anonymous
    ``op:*`` bucket and the per-scope wire attribution silently
    degrades. While ``obs/commtime.py`` exists the
    ``dl4j_tpu_comm_*`` family block must exist in FAMILIES (rule 6
    already checks kinds — this catches the block being deleted
    outright), every ``dl4j_tpu_comm_*`` token in
    ``tools/tpu_watch.py``/``docs/OPS.md`` must resolve against the
    table, and ``tpu_watch`` must reference at least one comm family
    — a wire-bound regression with no dashboard surface lands
    unwatched.

12. **The elastic serving fleet stays routable and prefetch-warm.**
    The fleet layer's whole contract (``serving/fleet.py``,
    ARCHITECTURE.md §20) is that a replica is only visible to the
    router once every jitted entry point is AOT-warm, and that the
    routing plane is observable. Producer side: the module-level
    ``STARTUP_PREFETCH`` tuple literal must name exactly the
    scheduler's ``WARMUP_FEEDS`` keys (both directions — a builder
    missing from the prefetch table cold-traces on the respawned
    replica's first request; a stale entry advertises a warmup that
    cannot run), and inside ``ServingReplica.start`` the ``warmup``
    call must precede every lease acquisition (``renew`` /
    ``start_auto_renew``) — lease-before-warm would let the router
    route to a cold replica. Metric side: every
    ``dl4j_tpu_router_*`` / ``dl4j_tpu_serving_fleet_*`` family must
    be declared in FAMILIES *and* have a live emit site (rule 6's
    lockstep, re-checked here so deleting the fleet block fails with
    a fleet-specific message), at least one family of each prefix
    must exist while the fleet module does, every such token in
    ``tools/tpu_watch.py``/``docs/OPS.md`` must resolve, and
    ``tpu_watch`` must reference at least one router family — an
    unwatched routing plane sheds silently.

Exit status 0 = clean; 1 = violations (printed one per line).
"""
from __future__ import annotations

import ast
import sys
from pathlib import Path
from typing import List, Optional

REPO = Path(__file__).resolve().parent.parent
PACKAGE = REPO / "deeplearning4j_tpu"

# wall-clock (calendar) users, not step timers — keep this list short
# and justified:
TIME_TIME_ALLOWLIST = {
    # max-seconds termination condition compares against a deadline
    "train/earlystopping.py",
    # cluster-event records carry epoch timestamps for cross-host logs
    "train/fault_tolerance.py",
    # membership leases are CROSS-PROCESS deadlines: wall clock is the
    # only clock whose readings are comparable between hosts
    "resilience/elastic.py",
}

_OBS_EMITTERS = {"record_step", "record_etl", "record_worker_step",
                 "span", "add_span", "instant", "counter",
                 "observe_step"}

# listener/stats paths scanned by rule 3 — per-record host code where
# device reductions over params/grads are banned (obs/numerics.py is
# the sanctioned in-step home, outside this set by construction)
LISTENER_STATS_PATHS = {"train/stats.py", "train/listeners.py"}

# rule 4 target: the SPMD wrapper whose step builders must each have a
# WARMUP_FEEDS entry
WRAPPER_PATH = "parallel/wrapper.py"

# rule 5 source of truth: the site table + named-plan vocabulary
FAULTS_PATH = "resilience/faults.py"

# rule 6 source of truth: the metric-family registry table
METRICS_PATH = "obs/metrics.py"

# rule 7 target: the serving gateway package whose jitted entry
# points must all be sentried, builder-scoped, and warmup-fed
SERVING_DIR = "serving"

# rule 6: non-family dl4j_tpu_* tokens that legitimately appear in the
# watched docs/tools (file-name stems, not metric families) — keep
# short and justified:
FAMILY_TOKEN_ALLOWLIST = {
    # the span tracer's default output file, dl4j_tpu_trace_<pid>.jsonl
    "dl4j_tpu_trace_",
}

# rule 8 annotation points: each listed function must contain a
# devtime.scope / jax.named_scope call. ONE site in each fit forward
# covers every registered layer type (and every zoo model built from
# layers); the remaining entries are the hand-rolled programs the fit
# forwards never trace. The ops/ entries are the PUBLIC Pallas kernels
# — rule 9 requires every registry kernel to be listed here, and this
# rule then keeps the kernel's own devtime scope from silently
# disappearing.
SCOPE_SITES = {
    "nn/multilayer.py": ("_forward",),
    "nn/graph.py": ("_forward",),
    "nn/decoder_infer.py": ("stack", "block", "logits"),
    "parallel/zero.py": ("scatter_mean", "gather"),
    "ops/pallas_kernels.py": ("flash_attention", "flash_block_fwd",
                              "flash_block_bwd",
                              "paged_decode_attention",
                              "latent_decode_attention",
                              "retention_decode", "ssm_decode",
                              "threshold_encode", "threshold_decode"),
    "ops/fused_norms.py": ("rms_norm", "add_rms_norm", "layer_norm"),
    "ops/moe.py": ("experts",),
}

# rule 8 source of truth for gap-report keys
DEVTIME_PATH = "obs/devtime.py"

# rule 9: the Pallas kernel library's home + its registry table
OPS_DIR = "ops"
KERNEL_REGISTRY_PATH = "ops/kernel_registry.py"

# rule 11: the communication observatory module, its metric-family
# prefix, the modules whose EXPLICIT collective emissions must be
# scope-annotated (GSPMD-inserted collectives are attributed through
# named_scope metadata already), and the primitive names that count
# as an emission
COMMTIME_PATH = "obs/commtime.py"
COMM_FAMILY_PREFIX = "dl4j_tpu_comm_"
COLLECTIVE_SCOPE_PATHS = ("parallel/zero.py", "parallel/composed.py",
                          "parallel/compression.py")
COLLECTIVE_EMITTERS = frozenset({
    "psum", "pmean", "psum_scatter", "all_gather", "ppermute",
    "all_to_all", "pshuffle"})


def _calls(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            yield node


def _attr_chain(func: ast.AST) -> str:
    """Dotted name of a call target ('sentry.jit', 'obs.trace.add_span',
    'time.time') — '' for anything fancier."""
    parts: List[str] = []
    while isinstance(func, ast.Attribute):
        parts.append(func.attr)
        func = func.value
    if isinstance(func, ast.Name):
        parts.append(func.id)
        return ".".join(reversed(parts))
    return ""


def lint_file(path: Path, rel: str) -> List[str]:
    try:
        tree = ast.parse(path.read_text())
    except SyntaxError as e:
        return [f"{rel}: unparseable ({e})"]
    chains = [_attr_chain(c.func) for c in _calls(tree)]
    problems = []

    uses_sentry_jit = any(ch == "sentry.jit" or ch.endswith(".sentry.jit")
                          for ch in chains)
    emits_obs = any(ch.split(".")[-1] in _OBS_EMITTERS and
                    ("obs" in ch.split(".") or ch.startswith("trace."))
                    for ch in chains)
    if uses_sentry_jit and not emits_obs:
        problems.append(
            f"{rel}: builds sentry.jit hot paths but never emits an "
            "obs span/metric (obs.record_step / obs.span / "
            "obs.trace.add_span) — jitted wall time would be invisible "
            "to the telemetry spine")

    in_obs = rel.startswith("obs/")
    if not in_obs and rel not in TIME_TIME_ALLOWLIST:
        for c in _calls(tree):
            if _attr_chain(c.func) == "time.time":
                problems.append(
                    f"{rel}:{c.lineno}: time.time() outside obs/ — "
                    "use obs.now (the one step clock) or, for "
                    "calendar timestamps, datetime + an allowlist "
                    "entry here")

    if rel in LISTENER_STATS_PATHS:
        for c in _calls(tree):
            ch = _attr_chain(c.func)
            if ch.startswith("jnp.") or ch.startswith("jax.numpy.") \
                    or ch in ("jax.tree.map", "jax.tree_map"):
                problems.append(
                    f"{rel}:{c.lineno}: host-side device reduction "
                    f"({ch}) in a listener/stats path — per-layer "
                    "training health is computed in-step by the "
                    "numerics observatory (obs/numerics.py, the "
                    "allowlisted home); consume net.last_numerics / "
                    "obs.numerics.tree_norms scalars instead")

    if rel == WRAPPER_PATH:
        problems.extend(_lint_wrapper_warmup(tree, rel))
    return problems


def _lint_wrapper_warmup(tree: ast.AST, rel: str) -> List[str]:
    """Rule 4: every ``_build_*_step`` method on ParallelWrapper has a
    ``WARMUP_FEEDS`` entry (and no entry is stale), and ``warmup()``
    actually reads the table."""
    builders = set()
    warmup_reads_table = False
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and \
                node.name == "ParallelWrapper":
            for sub in ast.walk(node):
                if isinstance(sub, ast.FunctionDef):
                    if sub.name.startswith("_build_") and \
                            sub.name.endswith("_step"):
                        builders.add(sub.name)
                    if sub.name == "warmup":
                        warmup_reads_table = any(
                            isinstance(n, ast.Name)
                            and n.id == "WARMUP_FEEDS"
                            for n in ast.walk(sub))
    feeds = None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "WARMUP_FEEDS"
                for t in node.targets):
            if isinstance(node.value, ast.Dict):
                feeds = {k.value for k in node.value.keys
                         if isinstance(k, ast.Constant)
                         and isinstance(k.value, str)}
    problems = []
    if not builders:
        return problems
    if feeds is None:
        return [f"{rel}: no WARMUP_FEEDS dict literal — step variants "
                "have no warmup feeds and will cold-trace their first "
                "real batch"]
    for b in sorted(builders - feeds):
        problems.append(
            f"{rel}: step builder {b} has no WARMUP_FEEDS entry — its "
            "step signature cannot be AOT-warmed and the first real "
            "batch stalls the mesh on a cold trace")
    for b in sorted(feeds - builders):
        problems.append(
            f"{rel}: WARMUP_FEEDS entry {b!r} names no step builder — "
            "stale feed (renamed/removed variant?)")
    if not warmup_reads_table:
        problems.append(
            f"{rel}: warmup() never reads WARMUP_FEEDS — the feed "
            "table is dead and step variants cold-trace")
    return problems


def _parse_fault_vocabulary(faults_path: Path):
    """``(KNOWN_SITES literals, named-plan site patterns)`` straight
    from the AST of ``resilience/faults.py`` — the lint never imports
    the package."""
    tree = ast.parse(faults_path.read_text())
    declared: set = set()
    plan_patterns: set = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        names = {t.id for t in node.targets
                 if isinstance(t, ast.Name)}
        if "KNOWN_SITES" in names:
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, str):
                    declared.add(sub.value)
        if "NAMED_PLANS" in names and isinstance(node.value, ast.Dict):
            for v in node.value.values:
                spec = ""
                # string literal or implicit concatenation folds to one
                # Constant; anything fancier is skipped (plans are
                # plain literals by construction)
                if isinstance(v, ast.Constant) and \
                        isinstance(v.value, str):
                    spec = v.value
                for chunk in spec.split(";"):
                    chunk = chunk.strip()
                    if chunk:
                        plan_patterns.add(chunk.split(":")[0])
    return declared, plan_patterns


def _inject_sites(package_dir: Path):
    """Every literal ``faults.inject("<site>")`` call site in the
    package: ``{site: [rel:lineno, ...]}``."""
    sites: dict = {}
    for path in sorted(package_dir.rglob("*.py")):
        rel = path.relative_to(package_dir).as_posix()
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                # rule-agnostic: lint_file reports it
        for c in _calls(tree):
            ch = _attr_chain(c.func)
            if not ch.endswith(".inject"):
                continue
            base = ch.rsplit(".", 2)[-2] if "." in ch else ""
            if base not in ("faults", "_faults"):
                continue
            if c.args and isinstance(c.args[0], ast.Constant) and \
                    isinstance(c.args[0].value, str):
                sites.setdefault(c.args[0].value, []).append(
                    f"{rel}:{c.lineno}")
    return sites


def _lint_fault_sites(package_dir: Path,
                      tests_dir: Optional[Path]) -> List[str]:
    """Rule 5: declared ⊆ injected ⊆ declared, and every injected site
    is named by a plan or a test."""
    import fnmatch
    faults_path = package_dir / FAULTS_PATH
    if not faults_path.is_file():
        return []
    declared, plan_patterns = _parse_fault_vocabulary(faults_path)
    injected = _inject_sites(package_dir)
    problems: List[str] = []
    for site in sorted(set(injected) - declared):
        problems.append(
            f"{injected[site][0]}: faults.inject({site!r}) is not in "
            f"{FAULTS_PATH} KNOWN_SITES — no fault plan can ever "
            "target it (the parser rejects unknown literal sites)")
    for site in sorted(declared - set(injected)):
        problems.append(
            f"{FAULTS_PATH}: KNOWN_SITES entry {site!r} has no "
            "faults.inject() call site anywhere in the package — a "
            "dead site advertising a drill that cannot fire")
    test_text = ""
    if tests_dir is not None and Path(tests_dir).is_dir():
        test_text = "\n".join(
            p.read_text() for p in sorted(Path(tests_dir).glob("*.py")))
    for site in sorted(set(injected) & declared):
        planned = any(fnmatch.fnmatchcase(site, pat)
                      for pat in plan_patterns)
        tested = f'"{site}"' in test_text or f"'{site}'" in test_text
        if not planned and not tested:
            problems.append(
                f"{injected[site][0]}: fault site {site!r} is covered "
                "by no NAMED_PLANS rule and referenced by no test — "
                "an undrillable site rots as the code around it moves")
    return problems


def _parse_families(metrics_path: Path) -> Optional[dict]:
    """``{family: kind}`` from the FAMILIES dict literal in
    ``obs/metrics.py`` — AST only, the lint never imports the
    package. None when the file/table is absent (synthetic trees)."""
    if not metrics_path.is_file():
        return None
    tree = ast.parse(metrics_path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "FAMILIES"
                for t in node.targets):
            if isinstance(node.value, ast.Dict):
                out = {}
                for k, v in zip(node.value.keys, node.value.values):
                    if isinstance(k, ast.Constant) and \
                            isinstance(k.value, str) and \
                            isinstance(v, ast.Constant) and \
                            isinstance(v.value, str):
                        out[k.value] = v.value
                return out
    return None


_FAMILY_KINDS = ("counter", "gauge", "histogram")


def _family_emit_sites(package_dir: Path) -> dict:
    """Every place the package EMITS a metric family:
    ``{name: [(kind, "rel:lineno"), ...]}`` — registration calls
    (``REGISTRY.counter/gauge/histogram("name", ...)``), pull-time
    collector tuples (``("name", "kind", doc, samples)``), and
    aggregator family tables (dict literals named
    ``AGGREGATE_FAMILIES``)."""
    sites: dict = {}

    def add(name, kind, where):
        sites.setdefault(name, []).append((kind, where))

    for path in sorted(package_dir.rglob("*.py")):
        rel = path.relative_to(package_dir).as_posix()
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                # rule-agnostic: lint_file reports it
        for c in _calls(tree):
            ch = _attr_chain(c.func)
            parts = ch.split(".")
            if parts[-1] in _FAMILY_KINDS and "REGISTRY" in parts and \
                    c.args and isinstance(c.args[0], ast.Constant) and \
                    isinstance(c.args[0].value, str):
                add(c.args[0].value, parts[-1], f"{rel}:{c.lineno}")
        for node in ast.walk(tree):
            if isinstance(node, ast.Tuple) and len(node.elts) >= 3 \
                    and isinstance(node.elts[0], ast.Constant) \
                    and isinstance(node.elts[0].value, str) \
                    and node.elts[0].value.startswith("dl4j_tpu_") \
                    and isinstance(node.elts[1], ast.Constant) \
                    and node.elts[1].value in _FAMILY_KINDS:
                add(node.elts[0].value, node.elts[1].value,
                    f"{rel}:{node.lineno}")
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name)
                    and t.id == "AGGREGATE_FAMILIES"
                    for t in node.targets) and \
                    isinstance(node.value, ast.Dict):
                for k, v in zip(node.value.keys, node.value.values):
                    if isinstance(k, ast.Constant) and \
                            isinstance(k.value, str):
                        kind = v.value if isinstance(v, ast.Constant) \
                            else ""
                        add(k.value, kind, f"{rel}:{node.lineno}")
    return sites


_FAMILY_TOKEN_RE = None


def _family_tokens(text: str) -> List[str]:
    global _FAMILY_TOKEN_RE
    if _FAMILY_TOKEN_RE is None:
        import re
        _FAMILY_TOKEN_RE = re.compile(r"dl4j_tpu_\w*")
    return _FAMILY_TOKEN_RE.findall(text)


def _resolve_family(token: str, families: dict) -> bool:
    """A consumer token resolves when it is a declared family, a
    histogram sample (``_bucket``/``_sum``/``_count``), or a prefix
    filter matching at least one declared family."""
    if token in families:
        return True
    for suffix in ("_bucket", "_sum", "_count"):
        if token.endswith(suffix) and \
                families.get(token[:-len(suffix)]) == "histogram":
            return True
    return any(f.startswith(token) for f in families)


def _lint_metric_families(package_dir: Path,
                          tools_dir: Optional[Path],
                          docs_dir: Optional[Path]) -> List[str]:
    """Rule 6: emitted ⊆ declared ⊆ emitted (kinds matching), and
    every dl4j_tpu_* token tpu_watch/OPS.md consumes resolves."""
    families = _parse_families(package_dir / METRICS_PATH)
    if families is None:
        return []                   # no registry table (synthetic tree)
    problems: List[str] = []
    sites = _family_emit_sites(package_dir)
    for name in sorted(sites):
        for kind, where in sites[name]:
            if name not in families:
                problems.append(
                    f"{where}: metric family {name!r} is not declared "
                    f"in {METRICS_PATH} FAMILIES — stringly-typed "
                    "family drift (declare it there first)")
            elif kind and families[name] != kind:
                problems.append(
                    f"{where}: metric family {name!r} emitted as "
                    f"{kind} but declared {families[name]!r} in "
                    f"{METRICS_PATH} FAMILIES")
    for name in sorted(set(families) - set(sites)):
        problems.append(
            f"{METRICS_PATH}: FAMILIES entry {name!r} has no emit "
            "site anywhere in the package — a dead declaration "
            "advertising a metric that never exists")
    consumers = []
    if tools_dir is not None and (Path(tools_dir)
                                  / "tpu_watch.py").is_file():
        consumers.append(("tools/tpu_watch.py",
                          (Path(tools_dir) / "tpu_watch.py")
                          .read_text()))
    if docs_dir is not None and (Path(docs_dir) / "OPS.md").is_file():
        consumers.append(("docs/OPS.md",
                          (Path(docs_dir) / "OPS.md").read_text()))
    for label, text in consumers:
        for token in sorted(set(_family_tokens(text))):
            if token in FAMILY_TOKEN_ALLOWLIST:
                continue
            if not _resolve_family(token, families):
                problems.append(
                    f"{label}: references {token!r} which matches no "
                    f"family in {METRICS_PATH} FAMILIES — the "
                    "dashboard/runbook is watching a metric the code "
                    "does not emit")
    return problems


def _sentry_jit_calls(tree: ast.AST):
    for c in _calls(tree):
        ch = _attr_chain(c.func)
        if ch == "sentry.jit" or ch.endswith(".sentry.jit"):
            yield c


def _lint_serving_jits(package_dir: Path) -> List[str]:
    """Rule 7: in ``serving/``, (a) no raw ``jax.jit`` (the sentry
    must see every serving entry point), (b) every ``sentry.jit`` call
    lives inside a ``_build_*`` builder, (c) builders and the
    module-level ``WARMUP_FEEDS`` table match both ways, and (d) a
    ``warmup`` function reads the table."""
    serving = package_dir / SERVING_DIR
    if not serving.is_dir():
        return []
    problems: List[str] = []
    for path in sorted(serving.glob("*.py")):
        rel = f"{SERVING_DIR}/{path.name}"
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                # rule-agnostic: lint_file reports it
        for c in _calls(tree):
            ch = _attr_chain(c.func)
            if ch == "jax.jit" or ch.endswith(".jax.jit"):
                problems.append(
                    f"{rel}:{c.lineno}: raw jax.jit in serving/ — "
                    "every serving entry point must go through "
                    "sentry.jit (retrace accounting + AOT warmup); a "
                    "bare jit here is invisible to the zero-retrace "
                    "fence")
        jit_calls = list(_sentry_jit_calls(tree))
        if not jit_calls:
            continue
        # innermost enclosing FunctionDef per sentry.jit call
        builders = set()
        covered = set()
        warmup_reads_table = False
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            inside = [c for c in jit_calls
                      if any(c is sub for sub in ast.walk(node))]
            if node.name == "warmup":
                warmup_reads_table = warmup_reads_table or any(
                    isinstance(n, ast.Name) and n.id == "WARMUP_FEEDS"
                    for n in ast.walk(node))
            if not inside:
                continue
            # walking outer defs first would mark calls covered by a
            # non-builder wrapper; only _build_* functions count
            if node.name.startswith("_build_"):
                builders.add(node.name)
                covered.update(id(c) for c in inside)
        for c in jit_calls:
            if id(c) not in covered:
                problems.append(
                    f"{rel}:{c.lineno}: sentry.jit outside a "
                    "_build_* builder — the WARMUP_FEEDS table can't "
                    "govern it, so warmup() can never AOT-compile "
                    "this entry point and the first live request "
                    "cold-traces")
        feeds = None
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "WARMUP_FEEDS"
                    for t in node.targets):
                if isinstance(node.value, ast.Dict):
                    feeds = {k.value for k in node.value.keys
                             if isinstance(k, ast.Constant)
                             and isinstance(k.value, str)}
        if not builders:
            continue
        if feeds is None:
            problems.append(
                f"{rel}: builds sentried serving entry points but has "
                "no WARMUP_FEEDS dict literal — nothing declares the "
                "warmup feeds and the first live request cold-traces")
            continue
        for b in sorted(builders - feeds):
            problems.append(
                f"{rel}: serving builder {b} has no WARMUP_FEEDS "
                "entry — its entry point cannot be AOT-warmed and the "
                "first live request stalls on a cold trace")
        for b in sorted(feeds - builders):
            problems.append(
                f"{rel}: WARMUP_FEEDS entry {b!r} names no _build_* "
                "builder — stale feed (renamed/removed entry point?)")
        if not warmup_reads_table:
            problems.append(
                f"{rel}: no warmup() reads WARMUP_FEEDS — the feed "
                "table is dead and serving entry points cold-trace")
    return problems


def _lint_program_identities(package_dir: Path) -> List[str]:
    """Rule 7, the key that needs no trace: every entry point built in
    ``serving/scheduler.py``, and every ``*.train_loop`` anywhere in
    the package, passes ``identity=`` to ``sentry.jit`` — without one
    a warm start traces and lowers the program again only to find its
    executable (``perf/aot_store.py``)."""
    problems: List[str] = []
    for path in sorted(package_dir.rglob("*.py")):
        rel = path.relative_to(package_dir).as_posix()
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                # rule-agnostic: lint_file reports it
        for c in _sentry_jit_calls(tree):
            kws = {k.arg: k.value for k in c.keywords}
            name = kws.get("name")
            loop = (isinstance(name, ast.Constant)
                    and str(name.value).endswith("train_loop"))
            if (rel == SCHEDULER_PATH or loop) and "identity" not in kws:
                problems.append(
                    f"{rel}:{c.lineno}: sentry.jit without identity= — "
                    "a serving program or a train loop that does not "
                    "say what it is: every warm start traces and "
                    "lowers it again to find an executable it could "
                    "load by a key (perf/aot_store.py)")
    return problems


# rule 10: the spec-decode scheduler module and the metric-family
# prefixes its dashboard/runbook coverage is checked under
SCHEDULER_PATH = "serving/scheduler.py"
SPEC_FAMILY_PREFIXES = ("dl4j_tpu_serving_spec_",
                        "dl4j_tpu_serving_prefix_")


def _lint_spec_decode(package_dir: Path,
                      tools_dir: Optional[Path],
                      docs_dir: Optional[Path]) -> List[str]:
    """Rule 10: any ``_build_spec*`` builder in the serving scheduler
    implies a module-level ``SPEC_KS`` tuple literal (the admissible
    draft-width grid), a ``WARMUP_FEEDS`` entry for the builder, and a
    ``warmup()`` that references ``SPEC_KS`` — the warmed (k, bucket)
    signatures and the widths the constructor admits must come from
    the same table. Consumer side: spec/prefix family tokens in
    tpu_watch/OPS.md resolve, and each consumer watches at least one
    ``dl4j_tpu_serving_spec_*`` family."""
    sched = package_dir / SCHEDULER_PATH
    if not sched.is_file():
        return []
    try:
        tree = ast.parse(sched.read_text())
    except SyntaxError:
        return []                   # rule-agnostic: lint_file reports it
    problems: List[str] = []
    spec_builders = set()
    warmup_refs_grid = False
    feeds = None
    spec_ks: Optional[set] = None
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name.startswith("_build_spec"):
                spec_builders.add(node.name)
            elif node.name == "warmup":
                warmup_refs_grid = warmup_refs_grid or any(
                    isinstance(n, ast.Name) and n.id == "SPEC_KS"
                    for n in ast.walk(node))
        elif isinstance(node, ast.Assign):
            names = {t.id for t in node.targets
                     if isinstance(t, ast.Name)}
            if "SPEC_KS" in names and isinstance(
                    node.value, (ast.Tuple, ast.List)):
                spec_ks = {e.value for e in node.value.elts
                           if isinstance(e, ast.Constant)
                           and isinstance(e.value, int)}
            if "WARMUP_FEEDS" in names and isinstance(node.value,
                                                      ast.Dict):
                feeds = {k.value for k in node.value.keys
                         if isinstance(k, ast.Constant)
                         and isinstance(k.value, str)}
    if not spec_builders:
        return problems
    if not spec_ks:
        problems.append(
            f"{SCHEDULER_PATH}: has spec-decode builders "
            f"({', '.join(sorted(spec_builders))}) but no module-"
            "level SPEC_KS tuple literal — nothing pins admissible "
            "draft widths to the warmed k grid, so an arbitrary k "
            "cold-traces on its first live step")
    if feeds is not None:
        for b in sorted(spec_builders - feeds):
            problems.append(
                f"{SCHEDULER_PATH}: spec builder {b} has no "
                "WARMUP_FEEDS entry — its per-k executables are "
                "outside the warmup table and every configured k "
                "cold-traces mid-traffic")
    if spec_ks and not warmup_refs_grid:
        problems.append(
            f"{SCHEDULER_PATH}: warmup() never references SPEC_KS — "
            "the warmed spec signatures and the constructor's "
            "admissible k grid can silently drift apart")
    families = _parse_families(package_dir / METRICS_PATH)
    if families is None:
        return problems
    consumers = []
    if tools_dir is not None and (Path(tools_dir)
                                  / "tpu_watch.py").is_file():
        consumers.append(("tools/tpu_watch.py",
                          (Path(tools_dir) / "tpu_watch.py")
                          .read_text()))
    if docs_dir is not None and (Path(docs_dir) / "OPS.md").is_file():
        consumers.append(("docs/OPS.md",
                          (Path(docs_dir) / "OPS.md").read_text()))
    for label, text in consumers:
        tokens = sorted({t for t in _family_tokens(text)
                         if t.startswith(SPEC_FAMILY_PREFIXES)})
        for token in tokens:
            if not _resolve_family(token, families):
                problems.append(
                    f"{label}: references {token!r} which matches no "
                    f"family in {METRICS_PATH} FAMILIES — the "
                    "dashboard/runbook watches a spec-decode metric "
                    "the code does not emit")
        if not any(t.startswith("dl4j_tpu_serving_spec_")
                   for t in tokens):
            problems.append(
                f"{label}: no dl4j_tpu_serving_spec_* family "
                "referenced — the speculative-decode accept rate has "
                "no dashboard/runbook surface, so a draft-quality "
                "regression lands unwatched")
    return problems


_GAP_TOKEN_RE = None


def _parse_gap_keys(devtime_path: Path) -> Optional[set]:
    """``GAP_KEYS`` tuple literal from ``obs/devtime.py`` — AST only.
    None when the file/tuple is absent (synthetic trees)."""
    if not devtime_path.is_file():
        return None
    tree = ast.parse(devtime_path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "GAP_KEYS"
                for t in node.targets):
            if isinstance(node.value, (ast.Tuple, ast.List)):
                return {e.value for e in node.value.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str)}
    return None


def _scope_call(chain: str) -> bool:
    parts = chain.split(".")
    return (parts[-1] == "scope" and "devtime" in parts) or \
        parts[-1] == "named_scope"


def _lint_devtime_scopes(package_dir: Path,
                         tools_dir: Optional[Path],
                         docs_dir: Optional[Path]) -> List[str]:
    """Rule 8: annotation points annotated, devtime family block
    present, and consumer ``gap.<key>`` tokens resolve against
    GAP_KEYS."""
    global _GAP_TOKEN_RE
    problems: List[str] = []
    for rel, fn_names in sorted(SCOPE_SITES.items()):
        path = package_dir / rel
        if not path.is_file():
            continue                # synthetic tree: nothing to hold
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                # rule-agnostic: lint_file reports it
        for want in fn_names:
            found = annotated = False
            for node in ast.walk(tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        node.name == want:
                    found = True
                    if any(_scope_call(_attr_chain(c.func))
                           for c in _calls(node)):
                        annotated = True
            if not found:
                problems.append(
                    f"{rel}: SCOPE_SITES names function {want!r} "
                    "which no longer exists — update the rule-8 "
                    "table to the renamed annotation point")
            elif not annotated:
                problems.append(
                    f"{rel}: {want}() carries no devtime.scope / "
                    "jax.named_scope — device-time attribution loses "
                    "this path's layers (every op lands in the "
                    "unattributed op:* bucket)")
    families = _parse_families(package_dir / METRICS_PATH)
    devtime_keys = _parse_gap_keys(package_dir / DEVTIME_PATH)
    if (package_dir / DEVTIME_PATH).is_file() and families is not None:
        if not any(f.startswith("dl4j_tpu_devtime_")
                   for f in families):
            problems.append(
                f"{METRICS_PATH}: no dl4j_tpu_devtime_* family in "
                "FAMILIES — the device-time observatory has no "
                "metric surface (the block was deleted?)")
    if devtime_keys is None:
        return problems
    if _GAP_TOKEN_RE is None:
        import re
        _GAP_TOKEN_RE = re.compile(r"\bgap\.([a-z_]+)")
    consumers = []
    if tools_dir is not None and (Path(tools_dir)
                                  / "tpu_watch.py").is_file():
        consumers.append(("tools/tpu_watch.py",
                          (Path(tools_dir) / "tpu_watch.py")
                          .read_text()))
    if docs_dir is not None and (Path(docs_dir) / "OPS.md").is_file():
        consumers.append(("docs/OPS.md",
                          (Path(docs_dir) / "OPS.md").read_text()))
    for label, text in consumers:
        for token in sorted(set(_GAP_TOKEN_RE.findall(text))):
            if token not in devtime_keys:
                problems.append(
                    f"{label}: references gap-report key "
                    f"'gap.{token}' which is not in {DEVTIME_PATH} "
                    "GAP_KEYS — the runbook/dashboard is reading a "
                    "column the gap report does not emit")
    return problems


def _parse_kernel_registry(path: Path) -> Optional[dict]:
    """``{kernel: {field: str | tuple}}`` from the KERNEL_REGISTRY
    dict literal — AST only. None when the file/table is absent
    (synthetic trees)."""
    if not path.is_file():
        return None
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            named = any(isinstance(t, ast.Name)
                        and t.id == "KERNEL_REGISTRY"
                        for t in node.targets)
        elif isinstance(node, ast.AnnAssign):   # KERNEL_REGISTRY: ... =
            named = (isinstance(node.target, ast.Name)
                     and node.target.id == "KERNEL_REGISTRY"
                     and node.value is not None)
        else:
            continue
        if named:
            if not isinstance(node.value, ast.Dict):
                continue
            out = {}
            for k, v in zip(node.value.keys, node.value.values):
                if not (isinstance(k, ast.Constant)
                        and isinstance(k.value, str)
                        and isinstance(v, ast.Dict)):
                    continue
                entry = {}
                for fk, fv in zip(v.keys, v.values):
                    if not (isinstance(fk, ast.Constant)
                            and isinstance(fk.value, str)):
                        continue
                    if isinstance(fv, ast.Constant):
                        entry[fk.value] = fv.value
                    elif isinstance(fv, (ast.Tuple, ast.List)):
                        entry[fk.value] = tuple(
                            e.value for e in fv.elts
                            if isinstance(e, ast.Constant))
                out[k.value] = entry
            return out
    return None


def _is_pallas_call(chain: str) -> bool:
    return chain == "pallas_call" or chain.endswith(".pallas_call")


def _public_kernels(tree: ast.AST):
    """Public kernel surface of one ops module: non-underscore
    module-level functions that reach a ``pallas_call`` directly or
    through PRIVATE (underscore) module-level helpers — reachability
    stops at public functions, so a bench helper calling the public
    kernels is a consumer, not a kernel. Returns
    ``{fn_name: scope_literals_emitted_inside}``."""
    fns = {n.name: n for n in tree.body
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    direct = {}
    callees = {}
    for name, node in fns.items():
        chains = [_attr_chain(c.func) for c in _calls(node)]
        direct[name] = any(_is_pallas_call(ch) for ch in chains)
        # module-local calls appear as bare names
        callees[name] = {ch for ch in chains if ch in fns}

    def reaches(name, seen=()):
        if direct.get(name):
            return True
        if name in seen:
            return False
        for g in callees.get(name, ()):
            if g.startswith("_") and reaches(g, seen + (name,)):
                return True
        return False

    out = {}
    for name, node in fns.items():
        if name.startswith("_") or not reaches(name):
            continue
        scopes = set()
        for c in _calls(node):
            if _scope_call(_attr_chain(c.func)) and c.args and \
                    isinstance(c.args[0], ast.Constant) and \
                    isinstance(c.args[0].value, str):
                scopes.add(c.args[0].value)
        out[name] = scopes
    return out


def _lint_kernel_registry(package_dir: Path,
                          tests_dir: Optional[Path]) -> List[str]:
    """Rule 9 (see module doc): pallas containment + registry/kernel
    lockstep + fallback/parity/scope resolution."""
    problems: List[str] = []
    ops_dir = package_dir / OPS_DIR
    # (a) no pallas_call outside ops/
    for path in sorted(package_dir.rglob("*.py")):
        rel = path.relative_to(package_dir).as_posix()
        if rel.startswith(OPS_DIR + "/"):
            continue
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                # rule-agnostic: lint_file reports it
        for c in _calls(tree):
            if _is_pallas_call(_attr_chain(c.func)):
                problems.append(
                    f"{rel}:{c.lineno}: raw pl.pallas_call outside "
                    f"{OPS_DIR}/ — kernels live in the ops library "
                    "behind the dispatch-gate/fallback/parity "
                    "contract (ARCHITECTURE §17); move it there and "
                    "register it in ops/kernel_registry.py")
    registry = _parse_kernel_registry(
        package_dir / KERNEL_REGISTRY_PATH)
    if not ops_dir.is_dir():
        return problems
    # public kernels per ops module
    module_kernels: dict = {}      # rel -> {fn: scopes}
    any_pallas = False
    for path in sorted(ops_dir.glob("*.py")):
        rel = f"{OPS_DIR}/{path.name}"
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue
        if any(_is_pallas_call(_attr_chain(c.func))
               for c in _calls(tree)):
            any_pallas = True
        module_kernels[rel] = _public_kernels(tree)
    if registry is None:
        if any_pallas:
            problems.append(
                f"{KERNEL_REGISTRY_PATH}: missing (or no "
                "KERNEL_REGISTRY dict literal) while ops/ contains "
                "Pallas kernels — the kernel library has no "
                "fallback/parity/scope contract")
        return problems
    declared_by_module: dict = {}
    for kname, entry in registry.items():
        declared_by_module.setdefault(entry.get("module", ""),
                                      {})[kname] = entry
    # a registry entry pointing at a module that doesn't exist would
    # otherwise skip every per-module check below — dead entries must
    # be flagged no matter how they died
    for mod in sorted(set(declared_by_module) - set(module_kernels)):
        for kname in sorted(declared_by_module[mod]):
            problems.append(
                f"{KERNEL_REGISTRY_PATH}: entry {kname!r} declares "
                f"module {mod!r} which is not an ops/ module — stale "
                "registry entry (moved/removed/typo'd module path?)")
    for rel, kernels in sorted(module_kernels.items()):
        declared = declared_by_module.get(rel, {})
        for fn in sorted(set(kernels) - set(declared)):
            problems.append(
                f"{rel}: public kernel {fn}() reaches pallas_call but "
                f"has no KERNEL_REGISTRY entry in "
                f"{KERNEL_REGISTRY_PATH} — undeclared kernels ship "
                "without a fallback/parity/scope contract")
        for kname in sorted(set(declared) - set(kernels)):
            problems.append(
                f"{KERNEL_REGISTRY_PATH}: entry {kname!r} names no "
                f"public kernel in {rel} — stale registry entry "
                "(renamed/removed kernel?)")
        # per-entry contract
        mod_tree = ast.parse((package_dir / rel).read_text())
        defs = {n.name for n in ast.walk(mod_tree)
                if isinstance(n, (ast.FunctionDef,
                                  ast.AsyncFunctionDef))}
        for kname in sorted(set(declared) & set(kernels)):
            entry = declared[kname]
            fb = entry.get("fallback")
            if not fb or fb not in defs:
                problems.append(
                    f"{KERNEL_REGISTRY_PATH}: kernel {kname!r} "
                    f"declares fallback {fb!r} which is not a "
                    f"function in {rel} — the gate-off path has no "
                    "value-identical XLA implementation")
            parity = entry.get("parity", "")
            if tests_dir is not None and Path(tests_dir).is_dir():
                ok = False
                if "::" in parity:
                    tfile, tname = parity.split("::", 1)
                    tpath = Path(tests_dir) / Path(tfile).name
                    ok = tpath.is_file() and \
                        f"def {tname}" in tpath.read_text()
                if not ok:
                    problems.append(
                        f"{KERNEL_REGISTRY_PATH}: kernel {kname!r} "
                        f"parity reference {parity!r} resolves to no "
                        "test — an unverified kernel's outputs drift "
                        "silently from its fallback")
            scope_lit = entry.get("scope")
            if not scope_lit or scope_lit not in kernels[kname]:
                problems.append(
                    f"{KERNEL_REGISTRY_PATH}: kernel {kname!r} "
                    f"declares scope {scope_lit!r} but {kname}() in "
                    f"{rel} never emits it via devtime.scope — its "
                    "device time lands unattributed")
            site_fns = SCOPE_SITES.get(rel, ())
            if kname not in site_fns:
                problems.append(
                    f"{KERNEL_REGISTRY_PATH}: kernel {kname!r} is not "
                    f"listed in SCOPE_SITES[{rel!r}] "
                    "(tools/lint_instrumentation.py) — rule 8 cannot "
                    "keep its devtime scope from disappearing")
    return problems


def _lint_comm_observatory(package_dir: Path,
                           tools_dir: Optional[Path],
                           docs_dir: Optional[Path]) -> List[str]:
    """Rule 11 (see module doc): collective emissions scoped, comm
    family block present, comm consumer tokens resolve, and tpu_watch
    actually watches the plane."""
    problems: List[str] = []
    for rel in COLLECTIVE_SCOPE_PATHS:
        path = package_dir / rel
        if not path.is_file():
            continue                # synthetic tree: nothing to hold
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue                # rule-agnostic: lint_file reports it
        # a collective call is covered when ANY enclosing function
        # (ast.walk of an outer def sees nested defs' calls too)
        # carries a devtime.scope / named_scope call
        covered = set()
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            coll = [c for c in _calls(node)
                    if _attr_chain(c.func).split(".")[-1]
                    in COLLECTIVE_EMITTERS]
            if coll and any(_scope_call(_attr_chain(c.func))
                            for c in _calls(node)):
                covered.update(id(c) for c in coll)
        for c in _calls(tree):
            ch = _attr_chain(c.func)
            if ch.split(".")[-1] not in COLLECTIVE_EMITTERS or \
                    id(c) in covered:
                continue
            problems.append(
                f"{rel}:{c.lineno}: collective emission ({ch}) outside "
                "any devtime.scope / jax.named_scope-carrying function "
                "— the communication observatory's wire ledger can "
                "only attribute these bytes to the anonymous op:* "
                "bucket; wrap the emitting phase in a devtime scope")
    families = _parse_families(package_dir / METRICS_PATH)
    if not (package_dir / COMMTIME_PATH).is_file() or families is None:
        return problems
    if not any(f.startswith(COMM_FAMILY_PREFIX) for f in families):
        problems.append(
            f"{METRICS_PATH}: no {COMM_FAMILY_PREFIX}* family in "
            "FAMILIES — the communication observatory has no metric "
            "surface (the block was deleted?)")
    consumers = []
    if tools_dir is not None and (Path(tools_dir)
                                  / "tpu_watch.py").is_file():
        consumers.append(("tools/tpu_watch.py",
                          (Path(tools_dir) / "tpu_watch.py")
                          .read_text()))
    if docs_dir is not None and (Path(docs_dir) / "OPS.md").is_file():
        consumers.append(("docs/OPS.md",
                          (Path(docs_dir) / "OPS.md").read_text()))
    for label, text in consumers:
        tokens = sorted({t for t in _family_tokens(text)
                         if t.startswith(COMM_FAMILY_PREFIX)})
        for token in tokens:
            if not _resolve_family(token, families):
                problems.append(
                    f"{label}: references {token!r} which matches no "
                    f"family in {METRICS_PATH} FAMILIES — the "
                    "dashboard/runbook watches a comm metric the code "
                    "does not emit")
        if label == "tools/tpu_watch.py" and not tokens:
            problems.append(
                f"{label}: no {COMM_FAMILY_PREFIX}* family referenced "
                "— the wire-byte/link-utilization plane has no "
                "dashboard surface, so a wire-bound regression lands "
                "unwatched")
    return problems


# rule 12: the elastic serving fleet module, the metric-family
# prefixes of its routing/supervision plane, and the call names that
# count as acquiring a membership lease
FLEET_PATH = "serving/fleet.py"
FLEET_FAMILY_PREFIXES = ("dl4j_tpu_router_", "dl4j_tpu_serving_fleet_")
LEASE_CALLS = frozenset({"renew", "start_auto_renew"})


def _lint_serving_fleet(package_dir: Path,
                        tools_dir: Optional[Path],
                        docs_dir: Optional[Path]) -> List[str]:
    """Rule 12 (see module doc): STARTUP_PREFETCH mirrors
    WARMUP_FEEDS, ServingReplica.start warms before it leases, the
    router/fleet metric surface exists with live emit sites, fleet
    consumer tokens resolve, and tpu_watch watches the router."""
    fleet = package_dir / FLEET_PATH
    if not fleet.is_file():
        return []
    try:
        tree = ast.parse(fleet.read_text())
    except SyntaxError:
        return []                   # rule-agnostic: lint_file reports it
    problems: List[str] = []

    # -- prefetch table mirrors the scheduler's warmup feeds ----------
    prefetch: Optional[set] = None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "STARTUP_PREFETCH"
                for t in node.targets):
            if isinstance(node.value, (ast.Tuple, ast.List)):
                prefetch = {e.value for e in node.value.elts
                            if isinstance(e, ast.Constant)
                            and isinstance(e.value, str)}
    if prefetch is None:
        problems.append(
            f"{FLEET_PATH}: no module-level STARTUP_PREFETCH tuple "
            "literal — the replica spawn path has no declared AOT "
            "prefetch table, so a cold respawn's first request traces "
            "live")
    feeds: Optional[set] = None
    sched = package_dir / SCHEDULER_PATH
    if sched.is_file():
        try:
            stree = ast.parse(sched.read_text())
        except SyntaxError:
            stree = None            # rule-agnostic: lint_file reports it
        if stree is not None:
            for node in ast.walk(stree):
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name)
                        and t.id == "WARMUP_FEEDS"
                        for t in node.targets) and \
                        isinstance(node.value, ast.Dict):
                    feeds = {k.value for k in node.value.keys
                             if isinstance(k, ast.Constant)
                             and isinstance(k.value, str)}
    if prefetch is not None and feeds is not None:
        for b in sorted(feeds - prefetch):
            problems.append(
                f"{FLEET_PATH}: scheduler builder {b} is missing from "
                "STARTUP_PREFETCH — a respawned replica passes the "
                "readiness gate with that entry point cold and its "
                "first live request stalls on a trace")
        for b in sorted(prefetch - feeds):
            problems.append(
                f"{FLEET_PATH}: STARTUP_PREFETCH entry {b!r} names no "
                f"WARMUP_FEEDS builder in {SCHEDULER_PATH} — stale "
                "prefetch entry (renamed/removed entry point?)")

    # -- warm-before-lease ordering inside ServingReplica.start -------
    for node in ast.walk(tree):
        if not (isinstance(node, ast.ClassDef)
                and node.name == "ServingReplica"):
            continue
        for fn in node.body:
            if not (isinstance(fn, (ast.FunctionDef,
                                    ast.AsyncFunctionDef))
                    and fn.name == "start"):
                continue
            warm = [c.lineno for c in _calls(fn)
                    if _attr_chain(c.func).split(".")[-1] == "warmup"]
            lease = [c.lineno for c in _calls(fn)
                     if _attr_chain(c.func).split(".")[-1]
                     in LEASE_CALLS]
            if not warm:
                problems.append(
                    f"{FLEET_PATH}: ServingReplica.start never calls "
                    "warmup() — replicas take leases cold and the "
                    "router routes live traffic onto untraced entry "
                    "points")
            elif lease and min(lease) < min(warm):
                problems.append(
                    f"{FLEET_PATH}:{min(lease)}: ServingReplica.start "
                    "acquires its membership lease before warmup() — "
                    "the router sees the replica as live while every "
                    "entry point is still cold; warm first, lease "
                    "last")

    # -- metric surface + consumer coverage ---------------------------
    families = _parse_families(package_dir / METRICS_PATH)
    if families is None:
        return problems
    emits = _family_emit_sites(package_dir)
    for prefix in FLEET_FAMILY_PREFIXES:
        if not any(f.startswith(prefix) for f in families):
            problems.append(
                f"{METRICS_PATH}: no {prefix}* family in FAMILIES — "
                "the serving-fleet plane has no metric surface (the "
                "block was deleted?)")
    for fam in sorted(f for f in families
                      if f.startswith(FLEET_FAMILY_PREFIXES)):
        if fam not in emits:
            problems.append(
                f"{METRICS_PATH}: fleet family {fam!r} is declared "
                "but never emitted — the router/supervisor path that "
                "fed it was deleted and the fleet dashboard reads a "
                "dead column")
    consumers = []
    if tools_dir is not None and (Path(tools_dir)
                                  / "tpu_watch.py").is_file():
        consumers.append(("tools/tpu_watch.py",
                          (Path(tools_dir) / "tpu_watch.py")
                          .read_text()))
    if docs_dir is not None and (Path(docs_dir) / "OPS.md").is_file():
        consumers.append(("docs/OPS.md",
                          (Path(docs_dir) / "OPS.md").read_text()))
    for label, text in consumers:
        tokens = sorted({t for t in _family_tokens(text)
                         if t.startswith(FLEET_FAMILY_PREFIXES)})
        for token in tokens:
            if not _resolve_family(token, families):
                problems.append(
                    f"{label}: references {token!r} which matches no "
                    f"family in {METRICS_PATH} FAMILIES — the "
                    "dashboard/runbook watches a fleet metric the "
                    "code does not emit")
        if label == "tools/tpu_watch.py" and not any(
                t.startswith("dl4j_tpu_router_") for t in tokens):
            problems.append(
                f"{label}: no dl4j_tpu_router_* family referenced — "
                "the routing plane has no dashboard surface, so "
                "structural sheds and re-route storms land unwatched")
    return problems


def run(package_dir: Path = PACKAGE,
        tests_dir: Optional[Path] = None,
        tools_dir: Optional[Path] = None,
        docs_dir: Optional[Path] = None) -> List[str]:
    problems: List[str] = []
    for path in sorted(package_dir.rglob("*.py")):
        rel = path.relative_to(package_dir).as_posix()
        problems.extend(lint_file(path, rel))
    if package_dir == PACKAGE:
        if tests_dir is None:
            tests_dir = REPO / "tests"
        if tools_dir is None:
            tools_dir = REPO / "tools"
        if docs_dir is None:
            docs_dir = REPO / "docs"
    problems.extend(_lint_fault_sites(package_dir, tests_dir))
    problems.extend(_lint_metric_families(package_dir, tools_dir,
                                          docs_dir))
    problems.extend(_lint_serving_jits(package_dir))
    problems.extend(_lint_program_identities(package_dir))
    problems.extend(_lint_spec_decode(package_dir, tools_dir,
                                      docs_dir))
    problems.extend(_lint_devtime_scopes(package_dir, tools_dir,
                                         docs_dir))
    problems.extend(_lint_kernel_registry(package_dir, tests_dir))
    problems.extend(_lint_comm_observatory(package_dir, tools_dir,
                                           docs_dir))
    problems.extend(_lint_serving_fleet(package_dir, tools_dir,
                                        docs_dir))
    return problems


def main() -> int:
    problems = run()
    for p in problems:
        print(p)
    if problems:
        print(f"{len(problems)} instrumentation lint violation(s)")
        return 1
    print("instrumentation lint: clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Tier-1 fence: every ``sentry.jit`` hot path emits obs telemetry and
nothing outside ``obs/`` step-times with ``time.time()`` — run as part
of the suite so a future PR that adds an uninstrumented jitted path
(or reintroduces a second wall clock) fails CI loudly."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

import lint_instrumentation  # noqa: E402


def test_package_passes_instrumentation_lint():
    problems = lint_instrumentation.run()
    assert not problems, "\n".join(problems)


def test_lint_catches_uninstrumented_hot_path(tmp_path):
    (tmp_path / "hot.py").write_text(
        "from deeplearning4j_tpu.perf import sentry\n"
        "step = sentry.jit(lambda x: x)\n")
    (tmp_path / "clock.py").write_text(
        "import time\nstart = time.time()\n")
    (tmp_path / "fine.py").write_text(
        "from deeplearning4j_tpu.perf import sentry\n"
        "from deeplearning4j_tpu import obs\n"
        "step = sentry.jit(lambda x: x)\n"
        "obs.record_step('e', 0.0, 0.0, 0.0, 0.0)\n")
    problems = lint_instrumentation.run(tmp_path)
    assert len(problems) == 2
    assert any("hot.py" in p and "sentry.jit" in p for p in problems)
    assert any("clock.py" in p and "time.time()" in p
               for p in problems)


def test_lint_catches_step_variant_without_warmup_feed(tmp_path):
    """Rule 4: a ParallelWrapper step builder missing from
    WARMUP_FEEDS (or a stale feed, or a warmup() that ignores the
    table) fails the lint — new step signatures can't silently
    cold-trace their first real batch."""
    pdir = tmp_path / "parallel"
    pdir.mkdir()
    (pdir / "wrapper.py").write_text(
        "class ParallelWrapper:\n"
        "    def _build_sync_step(self):\n"
        "        pass\n"
        "    def _build_fancy_new_step(self):\n"
        "        pass\n"
        "    def warmup(self, specs):\n"
        "        return WARMUP_FEEDS\n"
        "WARMUP_FEEDS = {\n"
        "    '_build_sync_step': None,\n"
        "    '_build_removed_step': None,\n"
        "}\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("_build_fancy_new_step" in p and "WARMUP_FEEDS" in p
               for p in problems)
    assert any("_build_removed_step" in p and "stale" in p
               for p in problems)
    # dead table: warmup() that never reads WARMUP_FEEDS
    (pdir / "wrapper.py").write_text(
        "class ParallelWrapper:\n"
        "    def _build_sync_step(self):\n"
        "        pass\n"
        "    def warmup(self, specs):\n"
        "        return None\n"
        "WARMUP_FEEDS = {'_build_sync_step': None}\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("never reads WARMUP_FEEDS" in p for p in problems)


def _fault_tree(tmp_path, known_sites, plans, inject_calls,
                test_text=""):
    """Synthesize a package tree for rule 5: a resilience/faults.py
    declaring ``known_sites``/``plans``, a module making the given
    inject calls, and an optional tests dir."""
    rdir = tmp_path / "pkg" / "resilience"
    rdir.mkdir(parents=True)
    sites = ", ".join(repr(s) for s in known_sites)
    plan_lines = ", ".join(f"{k!r}: {v!r}" for k, v in plans.items())
    (rdir / "faults.py").write_text(
        f"KNOWN_SITES = frozenset({{{sites}}})\n"
        f"NAMED_PLANS = {{{plan_lines}}}\n"
        "def inject(site):\n    pass\n")
    body = "from pkg.resilience import faults\n" + "".join(
        f"faults.inject({s!r})\n" for s in inject_calls)
    (tmp_path / "pkg" / "consumer.py").write_text(body)
    tdir = tmp_path / "tests"
    tdir.mkdir(exist_ok=True)
    (tdir / "test_x.py").write_text(test_text)
    return tmp_path / "pkg", tdir


def test_lint_rule5_dead_and_undeclared_and_unplanned_sites(tmp_path):
    """Rule 5: a KNOWN_SITES entry with no call site is dead; an
    inject() of an undeclared site is untargetable; a declared+called
    site with neither a named plan nor a test reference is
    undrillable."""
    pkg, tdir = _fault_tree(
        tmp_path,
        known_sites=["step", "ghost", "orphan"],
        plans={"p1": "step:error=OSError:nth=1"},
        inject_calls=["step", "rogue", "orphan"])
    problems = lint_instrumentation.run(pkg, tdir)
    assert any("ghost" in p and "dead site" in p for p in problems)
    assert any("rogue" in p and "KNOWN_SITES" in p for p in problems)
    assert any("orphan" in p and "no NAMED_PLANS rule" in p
               for p in problems)
    # 'step' is planned: not flagged
    assert not any("'step'" in p for p in problems)


def test_lint_rule5_test_reference_and_glob_plan_cover(tmp_path):
    """A quoted site string in tests/ counts as coverage, and a glob
    plan rule (ckpt_*) covers every site it matches."""
    pkg, tdir = _fault_tree(
        tmp_path,
        known_sites=["ckpt_write", "ckpt_commit", "serving"],
        plans={"io": "ckpt_*:error=OSError:p=0.5"},
        inject_calls=["ckpt_write", "ckpt_commit", "serving"],
        test_text='PLAN = "serving:error=RuntimeError:nth=2"\n'
                  'SITE = "serving"\n')
    problems = lint_instrumentation.run(pkg, tdir)
    assert problems == []


def test_lint_rule5_real_package_sites_all_live_and_drillable():
    """The live package: every KNOWN_SITES entry (including the
    elastic layer's host_death/coordinator) is threaded and covered —
    asserted through the full run() already, but pin the vocabulary
    parse here so a refactor that moves the tables fails loudly."""
    declared, plan_pats = lint_instrumentation._parse_fault_vocabulary(
        lint_instrumentation.PACKAGE / "resilience" / "faults.py")
    assert {"host_death", "coordinator", "step",
            "worker_step"} <= declared
    injected = lint_instrumentation._inject_sites(
        lint_instrumentation.PACKAGE)
    assert declared == set(injected)


def _metrics_tree(tmp_path, families, body="", watch=None, ops=None):
    """Synthesize a package tree for rule 6: an obs/metrics.py with a
    FAMILIES dict + registrations, an optional extra module, and
    optional tools/tpu_watch.py + docs/OPS.md consumers."""
    obs_dir = tmp_path / "pkg" / "obs"
    obs_dir.mkdir(parents=True)
    fams = ", ".join(f"{k!r}: {v!r}" for k, v in families.items())
    (obs_dir / "metrics.py").write_text(
        f"FAMILIES = {{{fams}}}\n"
        "class MetricsRegistry:\n    pass\n"
        "REGISTRY = MetricsRegistry()\n" + body)
    tools_dir = docs_dir = None
    if watch is not None:
        tools_dir = tmp_path / "tools"
        tools_dir.mkdir()
        (tools_dir / "tpu_watch.py").write_text(watch)
    if ops is not None:
        docs_dir = tmp_path / "docs"
        docs_dir.mkdir()
        (docs_dir / "OPS.md").write_text(ops)
    return tmp_path / "pkg", tools_dir, docs_dir


def test_lint_rule6_undeclared_dead_and_kind_mismatch(tmp_path):
    """Rule 6: a registration of an undeclared family is drift; a
    FAMILIES entry with no emit site is dead; a kind mismatch between
    declaration and emit site is flagged."""
    pkg, _t, _d = _metrics_tree(
        tmp_path,
        families={"dl4j_tpu_a_total": "counter",
                  "dl4j_tpu_ghost_total": "counter",
                  "dl4j_tpu_b_depth": "gauge"},
        body='A = REGISTRY.counter("dl4j_tpu_a_total", "doc")\n'
             'B = REGISTRY.counter("dl4j_tpu_b_depth", "doc")\n'
             'R = REGISTRY.gauge("dl4j_tpu_rogue", "doc")\n')
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert any("dl4j_tpu_rogue" in p and "not declared" in p
               for p in problems)
    assert any("dl4j_tpu_ghost_total" in p and "no emit site" in p
               for p in problems)
    assert any("dl4j_tpu_b_depth" in p and "counter" in p
               for p in problems)
    assert not any("'dl4j_tpu_a_total'" in p for p in problems)


def test_lint_rule6_collector_tuples_and_aggregate_tables_count(
        tmp_path):
    """Pull-time collector tuples and AGGREGATE_FAMILIES dict entries
    are emit sites — they keep their declarations alive."""
    pkg, _t, _d = _metrics_tree(
        tmp_path,
        families={"dl4j_tpu_col_total": "counter",
                  "dl4j_tpu_agg_skew": "gauge"},
        body='def _collector():\n'
             '    yield ("dl4j_tpu_col_total", "counter", "d", [])\n'
             'AGGREGATE_FAMILIES = {"dl4j_tpu_agg_skew": "gauge"}\n')
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert problems == []


def test_lint_rule6_consumer_tokens_must_resolve(tmp_path):
    """Every dl4j_tpu_* token in tpu_watch/OPS.md must name a declared
    family — exactly, via a histogram sample suffix, or as a prefix
    filter; an unresolvable token is a dashboard watching nothing."""
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path,
        families={"dl4j_tpu_lat_seconds": "histogram",
                  "dl4j_tpu_numerics_x": "gauge"},
        body='H = REGISTRY.histogram("dl4j_tpu_lat_seconds", "d")\n'
             'G = REGISTRY.gauge("dl4j_tpu_numerics_x", "d")\n',
        watch='KEYS = ("dl4j_tpu_lat_seconds_count",\n'
              '        "dl4j_tpu_numerics_")\n'
              'BAD = "dl4j_tpu_never_emitted_total"\n',
        ops="Watch `dl4j_tpu_lat_seconds` and the\n"
            "`dl4j_tpu_retired_family` counter.\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests",
                                        tools_dir, docs_dir)
    assert any("tpu_watch" in p and "dl4j_tpu_never_emitted_total" in p
               for p in problems)
    assert any("OPS.md" in p and "dl4j_tpu_retired_family" in p
               for p in problems)
    # suffix + prefix + exact tokens all resolved
    assert not any("dl4j_tpu_lat_seconds" in p and "matches no" in p
                   for p in problems)
    assert not any("dl4j_tpu_numerics_" in p for p in problems)


def test_lint_rule6_real_package_families_all_declared():
    """The live package: the FAMILIES table parses and covers the
    standing families (pin the vocabulary so a refactor that moves
    the table fails loudly)."""
    fams = lint_instrumentation._parse_families(
        lint_instrumentation.PACKAGE / "obs" / "metrics.py")
    assert fams and fams["dl4j_tpu_step_latency_seconds"] == \
        "histogram"
    assert {"dl4j_tpu_collective_skew_seconds",
            "dl4j_tpu_fleet_snapshots_published_total",
            "dl4j_tpu_flight_recorder_dumps_total",
            "dl4j_tpu_mesh_epoch"} <= set(fams)
    sites = lint_instrumentation._family_emit_sites(
        lint_instrumentation.PACKAGE)
    assert set(sites) == set(fams)


def test_lint_catches_listener_side_device_reductions(tmp_path):
    """Rule 3: jnp / jax.tree.map reductions in listener/stats paths
    (the old StatsListener._prev_params pattern) are flagged; the
    numpy-over-leaves host histogram opt-in stays legal."""
    stats_dir = tmp_path / "train"
    stats_dir.mkdir()
    (stats_dir / "stats.py").write_text(
        "import jax\nimport jax.numpy as jnp\n"
        "def norms(params, prev):\n"
        "    upd = jax.tree.map(lambda a, b: a - b, params, prev)\n"
        "    return jnp.sqrt(sum(jnp.sum(jnp.square(l))\n"
        "                        for l in jax.tree.leaves(upd)))\n")
    (stats_dir / "listeners.py").write_text(
        "import jax\nimport numpy as np\n"
        "def hist(sub):\n"
        "    return np.concatenate([np.asarray(l).ravel()\n"
        "                           for l in jax.tree.leaves(sub)])\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("train/stats.py" in p and "jax.tree.map" in p
               for p in problems)
    assert any("train/stats.py" in p and "jnp." in p for p in problems)
    assert not any("train/listeners.py" in p for p in problems)


def test_lint_rule7_serving_jits_sentried_and_fed(tmp_path):
    """Rule 7: a raw jax.jit in serving/, a sentry.jit outside a
    _build_* builder, a builder without a WARMUP_FEEDS entry, a stale
    feed, and a warmup() that ignores the table are all flagged."""
    sdir = tmp_path / "serving"
    sdir.mkdir()
    (sdir / "bad.py").write_text(
        "import jax\n"
        "from deeplearning4j_tpu.perf import sentry\n"
        "from deeplearning4j_tpu import obs\n"
        "raw = jax.jit(lambda x: x)\n"
        "stray = sentry.jit(lambda x: x)\n"
        "obs.record_step('e', 0.0, 0.0, 0.0, 0.0)\n"
        "class S:\n"
        "    def _build_step_fn(self):\n"
        "        return sentry.jit(lambda x: x)\n"
        "    def _build_orphan_fn(self):\n"
        "        return sentry.jit(lambda x: x)\n"
        "    def warmup(self):\n"
        "        return None\n"
        "WARMUP_FEEDS = {'_build_step_fn': 'feed',\n"
        "                '_build_removed_fn': 'stale'}\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("bad.py:4" in p and "raw jax.jit" in p
               for p in problems)
    assert any("bad.py:5" in p and "outside a _build_" in p
               for p in problems)
    assert any("_build_orphan_fn" in p and "WARMUP_FEEDS" in p
               for p in problems)
    assert any("_build_removed_fn" in p and "stale" in p
               for p in problems)
    assert any("no warmup() reads WARMUP_FEEDS" in p
               for p in problems)


def test_lint_rule7_clean_serving_module_passes(tmp_path):
    sdir = tmp_path / "serving"
    sdir.mkdir()
    (sdir / "good.py").write_text(
        "from deeplearning4j_tpu.perf import sentry\n"
        "from deeplearning4j_tpu import obs\n"
        "WARMUP_FEEDS = {'_build_step_fn': 'feed'}\n"
        "class S:\n"
        "    def _build_step_fn(self):\n"
        "        def step(x):\n"
        "            return x\n"
        "        return sentry.jit(step)\n"
        "    def warmup(self):\n"
        "        assert WARMUP_FEEDS\n"
        "        obs.record_step('e', 0.0, 0.0, 0.0, 0.0)\n"
        "        return 0\n")
    assert not lint_instrumentation.run(tmp_path)


def test_lint_rule7_missing_feed_table(tmp_path):
    sdir = tmp_path / "serving"
    sdir.mkdir()
    (sdir / "nofeeds.py").write_text(
        "from deeplearning4j_tpu.perf import sentry\n"
        "from deeplearning4j_tpu import obs\n"
        "obs.record_step('e', 0.0, 0.0, 0.0, 0.0)\n"
        "class S:\n"
        "    def _build_step_fn(self):\n"
        "        return sentry.jit(lambda x: x)\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("no WARMUP_FEEDS dict literal" in p for p in problems)


def test_lint_rule7_scheduler_and_train_loop_say_what_they_are(tmp_path):
    """Rule 7: an entry point built in serving/scheduler.py, or a
    train loop anywhere, without identity= is flagged; one with it,
    and any other entry point, is not."""
    pkg = tmp_path / "pkg"
    (pkg / "serving").mkdir(parents=True)
    (pkg / "nn").mkdir()
    (pkg / "serving" / "scheduler.py").write_text(
        "from deeplearning4j_tpu.perf import sentry\n"
        "from deeplearning4j_tpu import obs\n"
        "WARMUP_FEEDS = {'_build_step_fn': 'feed',\n"
        "                '_build_mute_fn': 'feed'}\n"
        "class S:\n"
        "    def _build_step_fn(self):\n"
        "        return sentry.jit(lambda x: x, name='s.step',\n"
        "                          identity=self._identity)\n"
        "    def _build_mute_fn(self):\n"
        "        return sentry.jit(lambda x: x, name='s.mute')\n"
        "    def warmup(self):\n"
        "        assert WARMUP_FEEDS\n"
        "        obs.record_step('e', 0.0, 0.0, 0.0, 0.0)\n")
    (pkg / "nn" / "net.py").write_text(
        "from deeplearning4j_tpu.perf import sentry\n"
        "from deeplearning4j_tpu import obs\n"
        "obs.record_step('e', 0.0, 0.0, 0.0, 0.0)\n"
        "said = sentry.jit(lambda x: x, name='Net.train_loop',\n"
        "                  identity=lambda: {})\n"
        "mute = sentry.jit(lambda x: x, name='Net.train_loop')\n"
        "step = sentry.jit(lambda x: x, name='Net.train_step')\n")
    problems = [p for p in lint_instrumentation.run(pkg)
                if "without identity=" in p]
    assert len(problems) == 2
    assert any("serving/scheduler.py:10" in p for p in problems)
    assert any("nn/net.py:6" in p for p in problems)


def _spec_scheduler(tmp_path, text):
    sdir = tmp_path / "pkg" / "serving"
    sdir.mkdir(parents=True, exist_ok=True)
    (sdir / "scheduler.py").write_text(text)
    return tmp_path / "pkg"


def test_lint_rule10_spec_builder_needs_grid_and_feed(tmp_path):
    """Rule 10: a _build_spec* builder without a module-level SPEC_KS
    tuple literal (nothing pins admissible draft widths to the warmed
    k grid) and without a WARMUP_FEEDS entry is flagged on both
    counts."""
    pkg = _spec_scheduler(
        tmp_path,
        "WARMUP_FEEDS = {'_build_step_fn': 'feed'}\n"
        "class S:\n"
        "    def _build_step_fn(self):\n"
        "        return None\n"
        "    def _build_spec_step_fn(self):\n"
        "        return None\n"
        "    def warmup(self):\n"
        "        return WARMUP_FEEDS\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert any("no module-level SPEC_KS tuple literal" in p
               for p in problems)
    assert any("_build_spec_step_fn" in p
               and "outside the warmup table" in p for p in problems)


def test_lint_rule10_warmup_must_walk_spec_grid(tmp_path):
    """Rule 10: SPEC_KS exists and the builder is fed, but warmup()
    never references the grid — the warmed spec signatures and the
    admissible widths can silently drift apart."""
    pkg = _spec_scheduler(
        tmp_path,
        "SPEC_KS = (2, 4)\n"
        "WARMUP_FEEDS = {'_build_spec_step_fn': 'feed'}\n"
        "class S:\n"
        "    def _build_spec_step_fn(self):\n"
        "        return None\n"
        "    def warmup(self):\n"
        "        return WARMUP_FEEDS\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert any("warmup() never references SPEC_KS" in p
               for p in problems)


# the real scheduler's rule-8 SCOPE_SITES entries apply to any tree
# carrying serving/scheduler.py, so the clean synthetic module must
# define all three annotation points with devtime scopes
_CLEAN_SPEC_SCHEDULER = (
    "SPEC_KS = (2, 4, 8)\n"
    "WARMUP_FEEDS = {'_build_spec_step_fn': 'feed'}\n"
    "class S:\n"
    "    def _build_step_fn(self):\n"
    "        return devtime.scope('serve.decode')\n"
    "    def _build_spec_step_fn(self):\n"
    "        return devtime.scope('serve.spec')\n"
    "    def _build_suffix_admit_fn(self):\n"
    "        return devtime.scope('serve.admit')\n"
    "    def warmup(self):\n"
    "        for k in SPEC_KS:\n"
    "            pass\n"
    "        return WARMUP_FEEDS\n")


def test_lint_rule10_clean_scheduler_passes(tmp_path):
    pkg = _spec_scheduler(tmp_path, _CLEAN_SPEC_SCHEDULER)
    assert not lint_instrumentation.run(pkg, tmp_path / "tests")


def test_lint_rule10_consumer_spec_tokens(tmp_path):
    """Rule 10 consumer side: a spec/prefix family token in
    tpu_watch/OPS.md that matches no FAMILIES entry is flagged with
    the spec-decode message, and a consumer that watches prefix
    families but no dl4j_tpu_serving_spec_* family leaves the accept
    rate without a dashboard/runbook surface."""
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path,
        families={"dl4j_tpu_serving_spec_accept_rate": "histogram",
                  "dl4j_tpu_serving_prefix_hits_total": "counter"},
        body='H = REGISTRY.histogram('
             '"dl4j_tpu_serving_spec_accept_rate", "d")\n'
             'C = REGISTRY.counter('
             '"dl4j_tpu_serving_prefix_hits_total", "d")\n',
        watch='KEYS = ("dl4j_tpu_serving_spec_accept_rate",\n'
              '        "dl4j_tpu_serving_spec_ghost_total")\n',
        ops="Watch `dl4j_tpu_serving_prefix_hits_total` only.\n")
    _spec_scheduler(tmp_path, _CLEAN_SPEC_SCHEDULER)
    problems = lint_instrumentation.run(pkg, tmp_path / "tests",
                                        tools_dir, docs_dir)
    assert any("tpu_watch" in p
               and "dl4j_tpu_serving_spec_ghost_total" in p
               and "spec-decode metric" in p for p in problems)
    assert any("OPS.md" in p
               and "no dl4j_tpu_serving_spec_* family" in p
               for p in problems)
    assert not any("tpu_watch" in p
                   and "no dl4j_tpu_serving_spec_* family" in p
                   for p in problems)


def test_lint_rule8_missing_scope_annotation(tmp_path):
    """Rule 8: a SCOPE_SITES function stripped of its devtime.scope /
    named_scope call fails the lint — attribution would silently lose
    that path's layers into the op:* bucket."""
    nn_dir = tmp_path / "nn"
    nn_dir.mkdir()
    (nn_dir / "multilayer.py").write_text(
        "class MultiLayerNetwork:\n"
        "    def _forward(self, params, x):\n"
        "        return x\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("multilayer.py" in p and "_forward" in p
               and "devtime.scope" in p for p in problems), problems
    # annotated variant passes (either spelling)
    (nn_dir / "multilayer.py").write_text(
        "from deeplearning4j_tpu import obs\n"
        "class MultiLayerNetwork:\n"
        "    def _forward(self, params, x):\n"
        "        with obs.devtime.scope('layer_0.Dense'):\n"
        "            return x\n")
    assert not lint_instrumentation.run(tmp_path)
    (nn_dir / "multilayer.py").write_text(
        "import jax\n"
        "class MultiLayerNetwork:\n"
        "    def _forward(self, params, x):\n"
        "        with jax.named_scope('dl4j.layer_0.Dense'):\n"
        "            return x\n")
    assert not lint_instrumentation.run(tmp_path)


def test_lint_rule8_renamed_annotation_point(tmp_path):
    """A SCOPE_SITES entry whose function vanished is reported — the
    table must follow refactors, not rot."""
    nn_dir = tmp_path / "nn"
    nn_dir.mkdir()
    (nn_dir / "decoder_infer.py").write_text(
        "def renamed_stack(params, toks):\n"
        "    pass\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("decoder_infer.py" in p and "'stack'" in p
               and "no longer exists" in p for p in problems)


def test_lint_rule8_gap_keys_must_resolve(tmp_path):
    """Every gap.<key> token OPS.md / tpu_watch references must be a
    GAP_KEYS member; devtime families must exist in FAMILIES."""
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path,
        {"dl4j_tpu_devtime_scope_share": "gauge"},
        body="REGISTRY.gauge('dl4j_tpu_devtime_scope_share', 'd',"
             " ('scope',))\n",
        ops="rank by gap.share, filter gap.pallas_candidate, and "
            "never gap.bogus_column\n")
    obs_dir = pkg / "obs"
    (obs_dir / "devtime.py").write_text(
        "GAP_KEYS = ('scope', 'share', 'pallas_candidate')\n")
    problems = lint_instrumentation.run(pkg, tools_dir=tools_dir,
                                        docs_dir=docs_dir)
    assert any("gap.bogus_column" in p and "GAP_KEYS" in p
               for p in problems), problems
    assert not any("gap.share" in p for p in problems)
    # deleting the devtime family block is caught
    (obs_dir / "metrics.py").write_text(
        "FAMILIES = {'dl4j_tpu_steps_total': 'counter'}\n"
        "class MetricsRegistry:\n    pass\n"
        "REGISTRY = MetricsRegistry()\n"
        "REGISTRY.counter('dl4j_tpu_steps_total', 'd')\n")
    problems = lint_instrumentation.run(pkg, tools_dir=tools_dir,
                                        docs_dir=docs_dir)
    assert any("no dl4j_tpu_devtime_* family" in p for p in problems)


def test_lint_rule8_real_package_annotation_points_hold():
    """The live package: every SCOPE_SITES function exists and is
    annotated, and the real OPS.md/tpu_watch gap keys resolve."""
    problems = [p for p in lint_instrumentation.run()
                if "devtime" in p or "gap." in p
                or "named_scope" in p]
    assert not problems, "\n".join(problems)


# -------------------------------------------------------------------------
# rule 9: Pallas kernels registered, contained, and contracted
# -------------------------------------------------------------------------

_CLEAN_KERNEL_MODULE = (
    "from jax.experimental import pallas as pl\n"
    "from deeplearning4j_tpu.obs import devtime\n"
    "def _rms_fwd_call(x):\n"
    "    return pl.pallas_call(None)(x)\n"
    "def rms_norm_reference(x, g):\n"
    "    return x\n"
    "def rms_norm(x, g):\n"
    "    with devtime.scope('ops.rms_norm'):\n"
    "        return _rms_fwd_call(x)\n")


def _kernel_registry_text(parity="tests/test_k.py::test_rms",
                          fallback="rms_norm_reference",
                          scope="ops.rms_norm",
                          name="rms_norm"):
    return (
        "KERNEL_REGISTRY = {\n"
        f"    '{name}': {{\n"
        "        'module': 'ops/fused_norms.py',\n"
        f"        'fallback': '{fallback}',\n"
        f"        'parity': '{parity}',\n"
        f"        'scope': '{scope}',\n"
        "        'closes': ('*.RMSNorm',),\n"
        "        'gate': 'fused_norm',\n"
        "    },\n"
        "}\n")


def _mk_kernel_tree(tmp_path, module=_CLEAN_KERNEL_MODULE,
                    registry=None, with_test=True):
    ops = tmp_path / "ops"
    ops.mkdir()
    # named fused_norms.py so the synthetic kernel resolves against
    # the real SCOPE_SITES table
    (ops / "fused_norms.py").write_text(module)
    (ops / "kernel_registry.py").write_text(
        registry if registry is not None else _kernel_registry_text())
    tests = tmp_path / "tests"
    tests.mkdir()
    if with_test:
        (tests / "test_k.py").write_text("def test_rms():\n    pass\n")
    return tests


def test_lint_rule9_clean_kernel_module_passes(tmp_path):
    tests = _mk_kernel_tree(tmp_path)
    problems = [p for p in lint_instrumentation.run(
        tmp_path, tests_dir=tests) if "kernel" in p.lower()
        or "pallas" in p.lower()]
    assert not problems, "\n".join(problems)


def test_lint_rule9_pallas_call_outside_ops(tmp_path):
    _mk_kernel_tree(tmp_path)
    (tmp_path / "rogue.py").write_text(
        "from jax.experimental import pallas as pl\n"
        "out = pl.pallas_call(None)(1)\n")
    problems = lint_instrumentation.run(tmp_path,
                                        tests_dir=tmp_path / "tests")
    assert any("rogue.py" in p and "pallas_call" in p
               for p in problems)


def test_lint_rule9_unregistered_public_kernel(tmp_path):
    tests = _mk_kernel_tree(
        tmp_path,
        module=_CLEAN_KERNEL_MODULE + (
            "def layer_norm(x, g):\n"
            "    with devtime.scope('ops.layer_norm'):\n"
            "        return _rms_fwd_call(x)\n"))
    problems = lint_instrumentation.run(tmp_path, tests_dir=tests)
    assert any("layer_norm" in p and "no KERNEL_REGISTRY entry" in p
               for p in problems)


def test_lint_rule9_stale_registry_entry(tmp_path):
    stale = (
        "    'gone_kernel': {\n"
        "        'module': 'ops/fused_norms.py',\n"
        "        'fallback': 'rms_norm_reference',\n"
        "        'parity': 'tests/test_k.py::test_rms',\n"
        "        'scope': 'ops.gone',\n"
        "        'closes': (),\n"
        "        'gate': 'always',\n"
        "    },\n}\n")
    base = _kernel_registry_text()
    assert base.endswith("}\n")
    tests = _mk_kernel_tree(tmp_path, registry=base[:-2] + stale)
    problems = lint_instrumentation.run(tmp_path, tests_dir=tests)
    assert any("gone_kernel" in p and "stale" in p for p in problems)


def test_lint_rule9_missing_fallback_parity_and_scope(tmp_path):
    tests = _mk_kernel_tree(
        tmp_path,
        registry=_kernel_registry_text(
            fallback="no_such_fn",
            parity="tests/test_k.py::test_missing",
            scope="ops.wrong_scope"))
    problems = lint_instrumentation.run(tmp_path, tests_dir=tests)
    assert any("no_such_fn" in p for p in problems)
    assert any("test_missing" in p and "parity" in p for p in problems)
    assert any("ops.wrong_scope" in p and "devtime.scope" in p
               for p in problems)


def test_lint_rule9_missing_registry_table(tmp_path):
    ops = tmp_path / "ops"
    ops.mkdir()
    (ops / "fused_norms.py").write_text(_CLEAN_KERNEL_MODULE)
    problems = lint_instrumentation.run(tmp_path)
    assert any("KERNEL_REGISTRY" in p and "missing" in p
               for p in problems)


# -------------------------------------------------------------------------
# rule 11: communication observatory — scoped collectives + comm plane
# -------------------------------------------------------------------------

def test_lint_rule11_unscoped_collective_emission(tmp_path):
    """Rule 11: a collective primitive called outside any scope-
    carrying function in a COLLECTIVE_SCOPE_PATHS module is flagged —
    its wire bytes could only land in the anonymous op:* bucket."""
    pdir = tmp_path / "parallel"
    pdir.mkdir()
    (pdir / "zero.py").write_text(
        "import jax\n"
        "from deeplearning4j_tpu.obs import devtime\n"
        "def scatter_mean(grads, axis_name):\n"
        "    with devtime.scope('zero.reduce_scatter'):\n"
        "        return jax.lax.psum_scatter(grads, axis_name)\n"
        "def gather(shards, axis_name):\n"
        "    return jax.lax.all_gather(shards, axis_name)\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("zero.py:7" in p and "all_gather" in p
               and "op:*" in p for p in problems), problems
    # the scoped site is NOT flagged
    assert not any("zero.py:5" in p for p in problems)
    # annotating the bare site clears the rule; a collective inside a
    # nested helper of a scoped function is covered too
    (pdir / "zero.py").write_text(
        "import jax\n"
        "from deeplearning4j_tpu.obs import devtime\n"
        "def scatter_mean(grads, axis_name):\n"
        "    with devtime.scope('zero.reduce_scatter'):\n"
        "        return jax.lax.psum_scatter(grads, axis_name)\n"
        "def gather(shards, axis_name):\n"
        "    def _pull(s):\n"
        "        return jax.lax.all_gather(s, axis_name)\n"
        "    with devtime.scope('zero.all_gather'):\n"
        "        return _pull(shards)\n")
    assert not lint_instrumentation.run(tmp_path)


def test_lint_rule11_module_level_collective_flagged(tmp_path):
    """A module-level (function-less) collective emission can never be
    covered by a scope — always flagged."""
    pdir = tmp_path / "parallel"
    pdir.mkdir()
    (pdir / "compression.py").write_text(
        "import jax\n"
        "TOTAL = jax.lax.psum(1, 'data')\n")
    problems = lint_instrumentation.run(tmp_path)
    assert any("compression.py:2" in p and "psum" in p
               for p in problems), problems


def test_lint_rule11_comm_family_block_and_consumer_tokens(tmp_path):
    """While obs/commtime.py exists: the dl4j_tpu_comm_* block must
    exist in FAMILIES, comm tokens in tpu_watch/OPS.md must resolve,
    and tpu_watch must watch at least one comm family."""
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path,
        families={"dl4j_tpu_comm_scope_wire_bytes": "gauge"},
        body='G = REGISTRY.gauge('
             '"dl4j_tpu_comm_scope_wire_bytes", "d")\n',
        watch='KEYS = ("dl4j_tpu_comm_scope_wire_bytes",\n'
              '        "dl4j_tpu_comm_ghost_total")\n',
        ops="Watch `dl4j_tpu_comm_retired_gauge` here.\n")
    (pkg / "obs" / "commtime.py").write_text("WIRE = 1\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests",
                                        tools_dir, docs_dir)
    assert any("tpu_watch" in p and "dl4j_tpu_comm_ghost_total" in p
               and "comm metric" in p for p in problems), problems
    assert any("OPS.md" in p and "dl4j_tpu_comm_retired_gauge" in p
               for p in problems)
    assert not any("dl4j_tpu_comm_scope_wire_bytes" in p
                   for p in problems)
    # no comm family block at all while commtime.py exists → flagged,
    # and a tpu_watch with no comm token leaves the plane unwatched
    pkg2 = tmp_path / "p2"
    p2, tools2, docs2 = _metrics_tree(
        pkg2, families={"dl4j_tpu_steps_total": "counter"},
        body='C = REGISTRY.counter("dl4j_tpu_steps_total", "d")\n',
        watch='KEYS = ("dl4j_tpu_steps_total",)\n')
    (p2 / "obs" / "commtime.py").write_text("WIRE = 1\n")
    problems = lint_instrumentation.run(p2, pkg2 / "tests",
                                        tools2, docs2)
    assert any("no dl4j_tpu_comm_* family in" in p
               for p in problems), problems
    assert any("tpu_watch" in p
               and "no dl4j_tpu_comm_* family referenced" in p
               for p in problems)


def test_lint_rule11_gated_off_without_commtime(tmp_path):
    """A tree without obs/commtime.py gets no comm-plane demands (the
    collective-scope check still applies to existing modules)."""
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path, families={"dl4j_tpu_steps_total": "counter"},
        body='C = REGISTRY.counter("dl4j_tpu_steps_total", "d")\n',
        watch='KEYS = ("dl4j_tpu_steps_total",)\n')
    assert not lint_instrumentation.run(pkg, tmp_path / "tests",
                                        tools_dir, docs_dir)


def test_lint_rule11_real_package_collectives_scoped():
    """The live package: every explicit collective emission in the
    scanned parallel/ modules is scope-covered and the comm plane has
    its dashboard surface."""
    problems = [p for p in lint_instrumentation.run()
                if "comm" in p or "collective emission" in p]
    assert not problems, "\n".join(problems)


# rule 12: the elastic serving fleet — prefetch table lockstep,
# warm-before-lease ordering, and the router/fleet metric surface

# the synthetic scheduler must satisfy rules 7/8/10 on its own (rule 8
# SCOPE_SITES applies to any tree carrying serving/scheduler.py)
_FLEET_SCHED = (
    "SPEC_KS = (2,)\n"
    "WARMUP_FEEDS = {'_build_step_fn': 'f',\n"
    "                '_build_spec_step_fn': 'f',\n"
    "                '_build_suffix_admit_fn': 'f'}\n"
    "class S:\n"
    "    def _build_step_fn(self):\n"
    "        return devtime.scope('serve.decode')\n"
    "    def _build_spec_step_fn(self):\n"
    "        return devtime.scope('serve.spec')\n"
    "    def _build_suffix_admit_fn(self):\n"
    "        return devtime.scope('serve.admit')\n"
    "    def warmup(self):\n"
    "        for k in SPEC_KS:\n"
    "            pass\n"
    "        return WARMUP_FEEDS\n")

_CLEAN_FLEET = (
    "STARTUP_PREFETCH = ('_build_step_fn', '_build_spec_step_fn',\n"
    "                    '_build_suffix_admit_fn')\n"
    "class ServingReplica:\n"
    "    def start(self):\n"
    "        self.gateway.warmup()\n"
    "        self.coord.renew()\n"
    "        self.coord.start_auto_renew()\n")


def _fleet_tree(tmp_path, fleet_text, sched_text=_FLEET_SCHED):
    sdir = tmp_path / "pkg" / "serving"
    sdir.mkdir(parents=True, exist_ok=True)
    (sdir / "fleet.py").write_text(fleet_text)
    if sched_text is not None:
        (sdir / "scheduler.py").write_text(sched_text)
    return tmp_path / "pkg"


def test_lint_rule12_clean_fleet_passes(tmp_path):
    pkg = _fleet_tree(tmp_path, _CLEAN_FLEET)
    assert not lint_instrumentation.run(pkg, tmp_path / "tests")


def test_lint_rule12_prefetch_mirrors_warmup_feeds(tmp_path):
    """Rule 12: a scheduler builder missing from STARTUP_PREFETCH
    cold-traces on the respawned replica's first request; a prefetch
    entry naming no builder is stale — both directions flagged."""
    pkg = _fleet_tree(
        tmp_path,
        "STARTUP_PREFETCH = ('_build_step_fn',\n"
        "                    '_build_spec_step_fn',\n"
        "                    '_build_ghost_fn')\n"
        "class ServingReplica:\n"
        "    def start(self):\n"
        "        self.gateway.warmup()\n"
        "        self.coord.renew()\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert any("_build_suffix_admit_fn" in p
               and "missing from STARTUP_PREFETCH" in p
               for p in problems)
    assert any("'_build_ghost_fn'" in p and "stale" in p
               for p in problems)


def test_lint_rule12_missing_prefetch_table(tmp_path):
    pkg = _fleet_tree(
        tmp_path,
        "class ServingReplica:\n"
        "    def start(self):\n"
        "        self.gateway.warmup()\n"
        "        self.coord.renew()\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert any("no module-level STARTUP_PREFETCH" in p
               for p in problems)


def test_lint_rule12_lease_before_warm_flagged(tmp_path):
    """Rule 12 ordering: a ServingReplica.start that acquires its
    membership lease before warmup() advertises a cold replica to the
    router; a start that never warms is flagged too."""
    pkg = _fleet_tree(
        tmp_path,
        "STARTUP_PREFETCH = ('_build_step_fn',\n"
        "                    '_build_spec_step_fn',\n"
        "                    '_build_suffix_admit_fn')\n"
        "class ServingReplica:\n"
        "    def start(self):\n"
        "        self.coord.renew()\n"
        "        self.gateway.warmup()\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert any("lease before warmup()" in p for p in problems)
    pkg = _fleet_tree(
        tmp_path,
        "STARTUP_PREFETCH = ('_build_step_fn',\n"
        "                    '_build_spec_step_fn',\n"
        "                    '_build_suffix_admit_fn')\n"
        "class ServingReplica:\n"
        "    def start(self):\n"
        "        self.coord.start_auto_renew()\n")
    problems = lint_instrumentation.run(pkg, tmp_path / "tests")
    assert any("never calls warmup()" in p for p in problems)


def test_lint_rule12_fleet_metric_surface(tmp_path):
    """Rule 12 metric side: a declared-but-unemitted fleet family, a
    consumer token matching no family, a tpu_watch with no router
    family, and a FAMILIES table with no serving-fleet prefix at all
    are each flagged with fleet-specific messages."""
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path,
        families={"dl4j_tpu_router_requests_total": "counter",
                  "dl4j_tpu_router_sheds_total": "counter"},
        body='C = REGISTRY.counter('
             '"dl4j_tpu_router_requests_total", "d")\n',
        watch='KEYS = ("dl4j_tpu_router_requests_total",)\n',
        ops="Watch `dl4j_tpu_router_ghost_total` here.\n")
    _fleet_tree(tmp_path, _CLEAN_FLEET)
    problems = lint_instrumentation.run(pkg, tmp_path / "tests",
                                        tools_dir, docs_dir)
    assert any("dl4j_tpu_router_sheds_total" in p
               and "never emitted" in p for p in problems)
    assert any("OPS.md" in p and "dl4j_tpu_router_ghost_total" in p
               and "fleet metric" in p for p in problems)
    assert any("no dl4j_tpu_serving_fleet_* family" in p
               for p in problems)
    # the watch references a router family: not flagged for that
    assert not any("tpu_watch" in p
                   and "no dl4j_tpu_router_* family" in p
                   for p in problems)


def test_lint_rule12_watch_must_reference_router(tmp_path):
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path,
        families={"dl4j_tpu_router_requests_total": "counter",
                  "dl4j_tpu_serving_fleet_spawns_total": "counter"},
        body='C = REGISTRY.counter('
             '"dl4j_tpu_router_requests_total", "d")\n'
             'S = REGISTRY.counter('
             '"dl4j_tpu_serving_fleet_spawns_total", "d")\n',
        watch='KEYS = ("dl4j_tpu_serving_fleet_spawns_total",)\n')
    _fleet_tree(tmp_path, _CLEAN_FLEET)
    problems = lint_instrumentation.run(pkg, tmp_path / "tests",
                                        tools_dir, docs_dir)
    assert any("tpu_watch" in p
               and "no dl4j_tpu_router_* family" in p
               for p in problems)


def test_lint_rule12_gated_off_without_fleet_module(tmp_path):
    """A tree without serving/fleet.py gets no fleet-plane demands."""
    pkg, tools_dir, docs_dir = _metrics_tree(
        tmp_path, families={"dl4j_tpu_steps_total": "counter"},
        body='C = REGISTRY.counter("dl4j_tpu_steps_total", "d")\n',
        watch='KEYS = ("dl4j_tpu_steps_total",)\n')
    assert not lint_instrumentation.run(pkg, tmp_path / "tests",
                                        tools_dir, docs_dir)


def test_lint_rule12_real_package_fleet_contract():
    """The live package: the prefetch table mirrors the warmup feeds,
    start() warms before it leases, and the router/fleet families all
    have emit sites + dashboard coverage."""
    problems = [p for p in lint_instrumentation.run()
                if "fleet" in p or "STARTUP_PREFETCH" in p
                or "router" in p]
    assert not problems, "\n".join(problems)


def test_lint_rule9_real_package_kernels_registered():
    """The live package: every public kernel in ops/ is registered
    with a resolvable fallback/parity/scope, and no pallas_call lives
    outside ops/."""
    problems = [p for p in lint_instrumentation.run()
                if "pallas" in p.lower() or "KERNEL_REGISTRY" in p]
    assert not problems, "\n".join(problems)

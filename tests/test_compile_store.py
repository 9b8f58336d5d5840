"""Content-addressed compile store (perf/compile_store.py): fence
semantics, corruption quarantine, crash consistency under kill -9
mid-``put`` (the checkpoint sweep idiom), and the compile-cache
routing that hands the store's fenced xla/ plane to JAX (ISSUE 18
satellite — the zero-cold-start substrate the serving fleet rides)."""
import json
import os
import subprocess
import sys
import time
import zlib
from pathlib import Path

from deeplearning4j_tpu.perf.compile_store import (CompileStore,
                                                   CORRUPT_DIR,
                                                   ENTRY_SUFFIX,
                                                   MAGIC,
                                                   from_env,
                                                   program_fingerprint)

REPO = Path(__file__).resolve().parent.parent


# =========================================================================
# fingerprint + round trip
# =========================================================================

def test_fingerprint_stable_and_order_insensitive():
    a = program_fingerprint(buckets=[8, 16], block=8, spec_k=2)
    b = program_fingerprint(spec_k=2, block=8, buckets=[8, 16])
    assert a == b and len(a) == 64
    assert a != program_fingerprint(buckets=[8, 32], block=8, spec_k=2)


def test_put_get_roundtrip_and_counters(tmp_path):
    store = CompileStore(tmp_path, jaxlib="1.0", topology="cpu")
    fp = program_fingerprint(model="m", buckets=[8])
    assert store.get(fp) is None                      # cold miss
    path = store.put(fp, b"payload-bytes")
    assert path.is_file() and path.suffix == ENTRY_SUFFIX
    assert store.get(fp) == b"payload-bytes"
    # overwrite publishes atomically over the old entry
    store.put(fp, b"v2")
    assert store.get(fp) == b"v2"
    c = store.counters()
    assert c["puts"] == 2 and c["hits"] == 2
    assert c["misses"] == 1 and c["quarantined"] == 0
    stats = store.stats()
    assert stats["objects"] == 1 and stats["fence"] == store.fence


def test_fence_mismatch_is_miss_not_damage(tmp_path):
    """A different jaxlib/topology reads a disjoint keyspace, and even
    a same-key entry whose header names another universe is a miss
    left IN PLACE — never quarantined (it is not damage)."""
    fp = program_fingerprint(model="m")
    old = CompileStore(tmp_path, jaxlib="0.4.36", topology="cpu")
    old.put(fp, b"old-binary-artifact")
    new = CompileStore(tmp_path, jaxlib="0.5.0", topology="cpu")
    assert new.fence != old.fence
    assert new.get(fp) is None                        # disjoint key
    assert old.get(fp) == b"old-binary-artifact"      # untouched
    # force a same-path fence-field mismatch: copy the old entry to
    # the new fence's path for this key
    new.entry_path(fp).write_bytes(old.entry_path(fp).read_bytes())
    assert new.get(fp) is None
    assert new.counters()["quarantined"] == 0
    assert new.entry_path(fp).is_file()               # left in place


def _corrupt(path: Path, mutate):
    path.write_bytes(mutate(path.read_bytes()))


def test_corrupt_entries_quarantined_then_recompile_path(tmp_path):
    """Every damage class (bad magic, truncated header, unparseable
    header, payload crc/size mismatch) is quarantined to
    ``<fence>/corrupt/`` and reported as a miss; a fresh ``put``
    (the recompile fallback) restores service on the same key."""
    store = CompileStore(tmp_path, jaxlib="1.0", topology="cpu")
    cases = [
        ("magic", lambda b: b"XXXX" + b[4:]),
        ("trunc", lambda b: b[:len(MAGIC) + 3]),
        ("header", lambda b: b.replace(MAGIC, MAGIC + b"not json", 1)),
        ("crc", lambda b: b[:-2] + bytes([b[-2] ^ 0xFF]) + b[-1:]),
    ]
    for i, (name, mutate) in enumerate(cases):
        fp = program_fingerprint(case=name)
        store.put(fp, b"payload-%d" % i + b"x" * 64)
        _corrupt(store.entry_path(fp), mutate)
        assert store.get(fp) is None, name
        assert not store.entry_path(fp).exists(), name
        # recompile fallback: the key serves again
        store.put(fp, b"recompiled")
        assert store.get(fp) == b"recompiled", name
    assert store.counters()["quarantined"] == len(cases)
    quarantined = list((store.fence_dir / CORRUPT_DIR).iterdir())
    assert len(quarantined) == len(cases)             # evidence kept


def test_quarantine_never_clobbers_prior_evidence(tmp_path):
    store = CompileStore(tmp_path, jaxlib="1.0", topology="cpu")
    fp = program_fingerprint(case="twice")
    for _ in range(2):
        store.put(fp, b"p" * 32)
        _corrupt(store.entry_path(fp), lambda b: b"XXXX" + b[4:])
        assert store.get(fp) is None
    names = [p.name for p in (store.fence_dir / CORRUPT_DIR).iterdir()]
    assert len(names) == 2 and len(set(names)) == 2


# =========================================================================
# crash consistency: kill -9 mid-put leaves old-or-absent, never torn
# =========================================================================

_KILL9_CHILD = r"""
import sys
sys.path.insert(0, %(repo)r)
from deeplearning4j_tpu.perf.compile_store import CompileStore
store = CompileStore(%(root)r, jaxlib="1.0", topology="cpu")
fp = %(fp)r
print("READY", flush=True)
i = 0
while True:                       # publish continuously until killed
    i += 1
    # generation-stamped payload, fat enough to widen the write window
    store.put(fp, (b"gen-%%08d|" %% i) + bytes([i %% 251]) * 65536)
    print("PUT %%d" %% i, flush=True)
"""


def test_kill9_mid_put_leaves_old_or_absent(tmp_path):
    """Acceptance: SIGKILL at ANY point during ``put`` leaves the
    entry old-or-absent — a subsequent ``get`` returns a complete
    generation's payload or a miss, and never quarantines (atomic
    publish means no torn entry ever lands at the final path)."""
    fp = program_fingerprint(sweep="kill9")
    for delay in (0.002, 0.01, 0.03):
        root = tmp_path / f"run_{int(delay * 1000)}"
        child = subprocess.Popen(
            [sys.executable, "-c", _KILL9_CHILD % {
                "repo": str(REPO), "root": str(root), "fp": fp}],
            stdout=subprocess.PIPE, text=True,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        puts = 0
        for line in child.stdout:
            if line.startswith("PUT"):
                puts += 1
                if puts >= 2:
                    break
        time.sleep(delay)         # land the kill mid-put-cycle
        child.kill()              # SIGKILL: no cleanup code runs
        child.wait(timeout=60)
        child.stdout.close()
        store = CompileStore(root, jaxlib="1.0", topology="cpu")
        got = store.get(fp)
        if got is not None:
            assert got.startswith(b"gen-") and len(got) == 65549, \
                f"kill@{delay}: torn payload"
            gen = int(got[4:12])
            assert got[13:] == bytes([gen % 251]) * 65536, \
                f"kill@{delay}: cross-generation tear"
        assert store.counters()["quarantined"] == 0, \
            f"kill@{delay}: atomic publish still landed a torn entry"


# =========================================================================
# env gating + compile-cache routing (subprocess: configure mutates
# process-global jax cache config)
# =========================================================================

def test_from_env_gating(tmp_path, monkeypatch):
    for off in ("", "0", "off", "none", "false", "disabled"):
        monkeypatch.setenv("DL4J_TPU_COMPILE_STORE", off)
        assert from_env() is None
    monkeypatch.delenv("DL4J_TPU_COMPILE_STORE", raising=False)
    assert from_env() is None
    monkeypatch.setenv("DL4J_TPU_COMPILE_STORE", str(tmp_path / "s"))
    store = from_env()
    assert store is not None
    assert store.root == tmp_path / "s"


_ROUTING_CHILD = r"""
import json, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
from deeplearning4j_tpu.perf import compile_cache
d = compile_cache.configure_from_env()
store = compile_cache.active_store()
print(json.dumps({
    "dir": d,
    "has_store": store is not None,
    "xla_dir": str(store.xla_dir) if store else None,
    "fence_in_stats": compile_cache.cache_stats().get("store_fence"),
    "jax_dir": jax.config.jax_compilation_cache_dir,
}))
"""


def test_compile_store_routes_persistent_cache(tmp_path):
    """DL4J_TPU_COMPILE_STORE supersedes the flat cache dir: the
    fenced xla/ plane becomes JAX's compilation cache dir (explicit
    opt-in, so it applies on CPU too)."""
    r = subprocess.run(
        [sys.executable, "-c", _ROUTING_CHILD % {"repo": str(REPO)}],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 DL4J_TPU_COMPILE_STORE=str(tmp_path / "store")))
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["has_store"] is True
    assert out["dir"] == out["xla_dir"] == out["jax_dir"]
    assert str(tmp_path / "store") in out["dir"]
    assert out["fence_in_stats"]


def test_compile_store_off_keeps_cpu_cache_disabled(tmp_path):
    """Without the store (and without DL4J_TPU_COMPILE_CACHE), a plain
    CPU-named process keeps the persistent cache off (the default
    dir is skipped there — see compile_cache.configure)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("DL4J_TPU_COMPILE_STORE", None)
    env.pop("DL4J_TPU_COMPILE_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-c", _ROUTING_CHILD % {"repo": str(REPO)}],
        capture_output=True, text=True, timeout=120, env=env)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["dir"] is None and out["has_store"] is False

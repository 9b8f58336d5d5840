"""Content-addressed compile store — compilation as a *fleet* asset.

The persistent XLA cache (``compile_cache.py``) makes compiles survive
one process's restarts; this module makes them survive replica churn
across a serving fleet. Two planes share one fenced root:

- **XLA plane** — ``<root>/<fence>/xla/`` is handed to JAX as
  ``jax_compilation_cache_dir`` (via ``compile_cache.configure`` when
  ``DL4J_TPU_COMPILE_STORE`` is set). The *fence* directory name bakes
  in ``(store format, jaxlib version, topology)``, so a jaxlib upgrade
  or a different device topology lands in a disjoint keyspace — a new
  binary can never deserialize a stale executable (the PyGraph
  version-fencing bar, arxiv 2503.19779).
- **Object plane** — ``<root>/<fence>/objects/<sha>.cse`` holds
  first-party content-addressed entries (the serving fleet's warm-plan
  manifests, AOT artifacts): ``sha = sha256(store_version, jaxlib,
  topology, program fingerprint)``. Entries are single files published
  with the ``resilience/checkpoint.py`` atomic idiom (same-dir dotted
  tmp, fsync, ``os.replace``, dir fsync), so a replica killed -9
  mid-``put`` leaves the old entry or no entry — never a truncated
  artifact another replica could load. A torn/corrupt entry found at
  ``get`` time is quarantined to ``<fence>/corrupt/`` and reported as
  a miss (fallback: recompile), mirroring the checkpoint scan.

Entry layout: ``MAGIC + header-JSON + "\\n" + payload`` where the
header carries ``{store_version, jaxlib, topology, fingerprint, size,
crc32}``; ``get`` re-derives the CRC before returning bytes. A header
whose fence fields mismatch the store's is a *fence miss* (wrong
universe, entry left alone); a payload that fails size/CRC is
*corruption* (quarantined).

See ARCHITECTURE.md §20 and the serving-fleet runbook in docs/OPS.md.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import zlib
from pathlib import Path
from typing import Any, Dict, Optional

STORE_VERSION = 1
MAGIC = b"DL4JCSE1\n"
ENTRY_SUFFIX = ".cse"
CORRUPT_DIR = "corrupt"


def default_jaxlib() -> str:
    """The jaxlib wheel version — the binary whose serialized
    executables the fence isolates."""
    import jaxlib
    return str(jaxlib.__version__)


def default_topology() -> str:
    """The NAMED platform string (``jax_platforms`` config, which reads
    ``JAX_PLATFORMS``) — never ``jax.devices()``, which would
    initialize a backend here. A process whose TPU was auto-detected
    names nothing and fences as ``"auto"``: that fence separates it
    from CPU-named processes and by jaxlib, but it is PER MACHINE
    TYPE — share one store root only among hosts with the same chips
    (JAX's own cache key still carries the device kind, so a foreign
    entry is a miss, never a wrong executable)."""
    import jax
    names = [p.strip() for p in
             str(jax.config.jax_platforms or "").split(",") if p.strip()]
    return "-".join(names) if names else "auto"


def _sanitize(part: str) -> str:
    return "".join(c if (c.isalnum() or c in "._-") else "_"
                   for c in str(part)) or "_"


def program_fingerprint(**parts: Any) -> str:
    """Stable fingerprint of a program's identity: sorted-key JSON of
    whatever the caller considers compile-relevant (model config,
    bucket grid, spec widths, block size...). Hash, not the JSON, is
    the key — callers never depend on the encoding."""
    blob = json.dumps(parts, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


class CompileStore:
    """One fence's view of the content-addressed store rooted at
    ``root``. Counters: puts / hits / misses (fence mismatch or
    absent) / quarantined (corrupt entries moved aside) / evicted
    (entries removed to keep ``max_bytes``)."""

    def __init__(self, root, *, jaxlib: Optional[str] = None,
                 topology: Optional[str] = None,
                 max_bytes: Optional[int] = None):
        self.root = Path(os.path.expanduser(str(root)))
        #: byte limit of the object plane (None: none): a ``put``
        #: removes the entries least recently got until it holds
        self.max_bytes = max_bytes
        self.jaxlib = jaxlib if jaxlib is not None else default_jaxlib()
        self.topology = (topology if topology is not None
                         else default_topology())
        self.fence = (f"v{STORE_VERSION}__jaxlib-"
                      f"{_sanitize(self.jaxlib)}__"
                      f"{_sanitize(self.topology)}")
        self.fence_dir = self.root / self.fence
        self.xla_dir = self.fence_dir / "xla"
        self.objects_dir = self.fence_dir / "objects"
        self._lock = threading.Lock()
        self._counters = {"puts": 0, "hits": 0, "misses": 0,
                          "quarantined": 0, "evicted": 0}
        self.xla_dir.mkdir(parents=True, exist_ok=True)
        self.objects_dir.mkdir(parents=True, exist_ok=True)

    # -- keys -------------------------------------------------------------
    def key(self, fingerprint: str) -> str:
        blob = json.dumps([STORE_VERSION, self.jaxlib, self.topology,
                           fingerprint], sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()

    def entry_path(self, fingerprint: str) -> Path:
        return self.objects_dir / (self.key(fingerprint) + ENTRY_SUFFIX)

    # -- write ------------------------------------------------------------
    def put(self, fingerprint: str, payload: bytes) -> Path:
        """Publish ``payload`` under ``fingerprint`` atomically: a
        reader (or a crash) observes the old entry, no entry, or the
        complete new entry — never a torn one."""
        from deeplearning4j_tpu.resilience.checkpoint import (
            atomic_write_bytes)
        header = {
            "store_version": STORE_VERSION,
            "jaxlib": self.jaxlib,
            "topology": self.topology,
            "fingerprint": fingerprint,
            "size": len(payload),
            "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
        }
        blob = MAGIC + json.dumps(header, sort_keys=True).encode() \
            + b"\n" + payload
        path = atomic_write_bytes(self.entry_path(fingerprint), blob)
        with self._lock:
            self._counters["puts"] += 1
        if self.max_bytes is not None:
            self._evict(keep=Path(path))
        return Path(path)

    def _evict(self, keep: Path) -> None:
        """Remove the entries least recently got (a ``get`` stamps
        the file's time) until the plane holds ``max_bytes``; the
        entry just put stays. A reader that loses a race with this
        sees a miss."""
        entries, total = [], 0
        for p in self.objects_dir.glob("*" + ENTRY_SUFFIX):
            try:
                st = p.stat()
            except OSError:
                continue
            total += st.st_size
            if p != keep:
                entries.append((st.st_mtime, st.st_size, p))
        for _mtime, size, p in sorted(entries):
            if total <= self.max_bytes:
                break
            try:
                p.unlink()
            except OSError:
                continue
            total -= size
            with self._lock:
                self._counters["evicted"] += 1

    # -- read -------------------------------------------------------------
    def get(self, fingerprint: str) -> Optional[bytes]:
        """Payload bytes, or None (miss). Fence-mismatched entries are
        misses and left in place (they belong to another universe);
        torn/corrupt entries are quarantined and reported as misses —
        the caller's fallback is always "recompile"."""
        path = self.entry_path(fingerprint)
        try:
            blob = path.read_bytes()
        except OSError:
            with self._lock:
                self._counters["misses"] += 1
            return None
        payload = self._validate(path, blob, fingerprint)
        if payload is not None and self.max_bytes is not None:
            try:                    # least-recent-load order
                os.utime(path)
            except OSError:
                pass
        with self._lock:
            self._counters["hits" if payload is not None
                           else "misses"] += 1
        return payload

    def _validate(self, path: Path, blob: bytes,
                  fingerprint: str) -> Optional[bytes]:
        if not blob.startswith(MAGIC):
            self._quarantine(path, "bad magic")
            return None
        rest = blob[len(MAGIC):]
        nl = rest.find(b"\n")
        if nl < 0:
            self._quarantine(path, "truncated header")
            return None
        try:
            header = json.loads(rest[:nl])
        except ValueError:
            self._quarantine(path, "unparseable header")
            return None
        if (header.get("store_version") != STORE_VERSION
                or header.get("jaxlib") != self.jaxlib
                or header.get("topology") != self.topology
                or header.get("fingerprint") != fingerprint):
            # version fence: a different universe's entry, not damage
            return None
        payload = rest[nl + 1:]
        if len(payload) != header.get("size") or \
                (zlib.crc32(payload) & 0xFFFFFFFF) != header.get("crc32"):
            self._quarantine(path, "size/crc mismatch")
            return None
        return payload

    def quarantine(self, fingerprint: str, reason: str) -> None:
        """Move ``fingerprint``'s entry aside: its bytes were sound
        but its reader could not use them (an executable the backend
        refuses to load)."""
        path = self.entry_path(fingerprint)
        if path.exists():
            self._quarantine(path, reason)

    def _quarantine(self, path: Path, reason: str) -> None:
        """Move a damaged entry to ``<fence>/corrupt/`` — out of every
        future ``get``, kept for post-mortems (the checkpoint-scan
        idiom)."""
        import shutil
        dest_dir = self.fence_dir / CORRUPT_DIR
        try:
            dest_dir.mkdir(parents=True, exist_ok=True)
            dest = dest_dir / path.name
            if dest.exists():       # keep prior evidence, don't clobber
                dest = dest_dir / f"{path.name}.{os.getpid()}"
            shutil.move(str(path), str(dest))
        except OSError:
            try:                    # at minimum get it out of the scan
                path.unlink()
            except OSError:
                return
        with self._lock:
            self._counters["quarantined"] += 1

    # -- reporting --------------------------------------------------------
    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def stats(self) -> Dict[str, Any]:
        """Disk + in-process view (walks the fence dir — once-per-run
        reporters only)."""
        objects = obj_bytes = 0
        for p in self.objects_dir.glob("*" + ENTRY_SUFFIX):
            objects += 1
            try:
                obj_bytes += p.stat().st_size
            except OSError:
                pass
        xla_entries = xla_bytes = 0
        if self.xla_dir.is_dir():
            for root, _dirs, files in os.walk(self.xla_dir):
                for f in files:
                    if f.endswith("-atime"):
                        continue
                    xla_entries += 1
                    try:
                        xla_bytes += os.path.getsize(
                            os.path.join(root, f))
                    except OSError:
                        pass
        fences = sorted(p.name for p in self.root.iterdir()
                        if p.is_dir()) if self.root.is_dir() else []
        out: Dict[str, Any] = {
            "root": str(self.root), "fence": self.fence,
            "fences": fences, "objects": objects,
            "object_bytes": obj_bytes, "xla_entries": xla_entries,
            "xla_bytes": xla_bytes,
        }
        out.update(self.counters())
        return out


def from_env() -> Optional[CompileStore]:
    """Store from ``DL4J_TPU_COMPILE_STORE`` (None when unset/off)."""
    from deeplearning4j_tpu import environment
    root = environment.get_flag("DL4J_TPU_COMPILE_STORE")
    if not root or str(root).strip().lower() in (
            "", "0", "off", "none", "false", "disabled"):
        return None
    return CompileStore(root)

"""Regression gate: importing ANY deeplearning4j_tpu submodule must not
initialise a jax backend or create device arrays.

A module-level ``jnp.asarray`` in ``autodiff/ops_registry_ext.py`` once
initialised the accelerator backend at import — on a host with a chip
that takes the chip (one process at a time) for whoever merely imports
SameDiff or the TF/ONNX importers. The reference's backend initialises on first use,
never at class-load (SURVEY §3.1 — upstream
``org.nd4j.linalg.factory.Nd4j`` static init defers native backend
selection to the first array op). This test fences the whole class of
bug: every submodule is imported in a subprocess (CPU platform, from
the environment conftest sets) and the jax backend cache must stay
empty afterwards.

Module enumeration is filesystem-based on purpose: ``pkgutil``'s
walkers import package ``__init__``s in THIS process and swallow
ImportErrors.
"""
import pathlib
import subprocess
import sys

import deeplearning4j_tpu

PKG_ROOT = pathlib.Path(deeplearning4j_tpu.__file__).parent


def _all_submodules():
    """Every importable module in the package, from the filesystem —
    nothing is imported here."""
    names = ["deeplearning4j_tpu"]
    for py in sorted(PKG_ROOT.rglob("*.py")):
        rel = py.relative_to(PKG_ROOT)
        parts = ("deeplearning4j_tpu",) + rel.with_suffix("").parts
        if parts[-1] == "__init__":
            parts = parts[:-1]
        names.append(".".join(parts))
    return sorted(set(names))


_CHECK = r"""
import jax
import importlib, sys
# Direct (non-getattr) access: if a jax upgrade moves this private
# cache the test must fail loudly, not pass vacuously.
from jax._src.xla_bridge import _backends

offenders = []
for name in sys.argv[1:]:
    importlib.import_module(name)
    # NB: jax.live_arrays() itself initialises a backend, so the only
    # safe detector is the backend cache (a device array cannot exist
    # without a backend entry).
    if _backends:
        offenders.append((name, list(_backends)))
        break  # first offender poisons the rest; report and stop
if offenders:
    print("BACKEND_TOUCHED_AT_IMPORT", offenders)
    raise SystemExit(1)
print("CLEAN", len(sys.argv) - 1)
"""


def test_no_submodule_initialises_backend_at_import():
    mods = _all_submodules()
    assert len(mods) > 60, f"submodule walk looks broken: {len(mods)}"
    r = subprocess.run(
        [sys.executable, "-c", _CHECK, *mods],
        capture_output=True, text=True, timeout=600,
        cwd=str(PKG_ROOT.parent),
    )
    assert r.returncode == 0, (
        f"a submodule touched the backend at import:\n{r.stdout}\n{r.stderr}"
    )
    assert "CLEAN" in r.stdout

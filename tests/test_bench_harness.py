"""The measurement entry points must not rot, and must not lie: each is
ONE process that needs the chip. Without a TPU they exit non-zero and
print no result — a CPU number is never written under a device metric
(and no structured "skip" with exit code 0). The perf-dossier smoke
path (tiny shapes, any backend, no MFU claim) is still executed as a
real subprocess in the slow lane.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(args, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=env, timeout=timeout,
        capture_output=True, text=True)


@pytest.mark.parametrize("script", [
    "bench.py", "tools/perf_dossier.py", "chip_smoke.py",
    "tools/flash_crossover.py"])
def test_device_entry_point_refuses_the_cpu(script):
    """JAX_PLATFORMS=cpu: non-zero exit, the reason on stderr, and no
    result line (no JSON, no ``"ok": true``, no ``"skipped"``)."""
    r = _run([script], timeout=120)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "TPU" in r.stderr, r.stderr[-2000:]
    assert '"ok"' not in r.stdout and '"skipped"' not in r.stdout
    assert not [l for l in r.stdout.splitlines()
                if l.startswith("{") and '"metric"' in l], r.stdout


@pytest.mark.slow
def test_perf_dossier_smoke_all_configs():
    r = _run(["tools/perf_dossier.py", "--smoke"])
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert "SMOKE RUN" in r.stdout
    for cfg in ("ResNet-50", "BERT-base", "charRNN", "flash-attn",
                "causal-LM"):
        assert cfg in r.stdout, (cfg, r.stdout[-2000:])
    assert "FAILED" not in r.stdout, r.stdout[-2000:]

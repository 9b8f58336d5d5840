"""Test config: the suite runs on the CPU backend with 8 virtual
devices (the SPMD tests' mesh), set BEFORE jax initializes a backend.

Mirrors the reference test strategy (SURVEY §4): same suite over every
backend. The chip is exercised by ``chip_smoke.py`` (one process per
chip), and the TPU compiler — for a described, not attached, device —
by ``tests/test_tpu_aot_compile.py``. ``JAX_PLATFORMS`` is the only
thing that names the platform; it is set here so that a bare
``pytest`` never takes an attached chip away from another process, and
so child processes the tests start inherit it.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the CPU has no entry in environment.DEVICE_PEAKS (an unknown device
# is an error there): the suite's roofline numbers are wiring checks
# against these NAMED constants, given as the explicit overrides
os.environ.setdefault("DL4J_TPU_PEAK_TFLOPS", "197")
os.environ.setdefault("DL4J_TPU_PEAK_HBM_GBS", "819")
os.environ.setdefault("DL4J_TPU_PEAK_ICI_GBS", "45")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: round-end harness fences (subprocess bench/dossier "
        "runs, ~8 min); deselect with -m 'not slow' for quick loops")


@pytest.fixture
def admits_alike_by_einsum_and_kernel(monkeypatch):
    """``check(model, net, block=, max_context=)``: a prompt of
    ``bucket / 2 + 1`` tokens admitted by the bucket prefill through
    the flash kernel's causal inference path (forced, interpret mode)
    gets the first token and the pages the einsum path gives it, the
    next tokens decoded from those pages are the same, and the pool
    holds no value that is not finite (the kernel leaves ZEROS in the
    rows past the prompt, whose K and V the layers above write)."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.serving import DecodeScheduler

    class Req:
        temperature = eos_id = None
        tenant = "t"

        def __init__(self, prompt, max_new):
            self.prompt, self.max_new = prompt, max_new
            self.tokens = []

        def push(self, tok):
            self.tokens.append(int(tok))

        def finish(self):
            pass

        def fail(self, e):
            raise e

    def check(model, net, *, block, max_context, bucket=64):
        t0 = bucket // 2 + 1
        prompt = (np.arange(t0, dtype=np.int32) * 7 + 3) % 64
        sides, counts = [], []
        for force in ("0", "1"):
            monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", force)
            calls, real = [], pk._prefill_fwd
            monkeypatch.setattr(
                pk, "_prefill_fwd",
                lambda *a, **kw: calls.append(1) or real(*a, **kw))
            sched = DecodeScheduler(model, net, max_slots=2, block=block,
                                    max_context=max_context)
            req = Req(prompt, 4)
            mark = obs.now()
            assert sched.admit(req)
            monkeypatch.setattr(pk, "_prefill_fwd", real)
            assert bool(calls) == (force == "1")
            counts.append([e for e in obs.trace.records(since=mark)
                           if e.name == "serving.prefill"][-1].counts)
            pool = [np.asarray(a, np.float32) for a in sched.pager.pool]
            assert all(np.isfinite(a).all() for a in pool)
            pages = sched.pager.owned(req)[:-(-t0 // block)]
            kept = pool[0][:, pages]
            kept = kept.reshape(kept.shape[0], -1, *kept.shape[3:])[:, :t0]
            monkeypatch.setenv("DL4J_TPU_KERNEL_FORCE", "0")
            for _ in range(3):
                sched.step()
            sched.drain()
            sides.append((list(req.tokens), kept))
        (plain_toks, plain_kept), (toks, kept) = sides
        assert toks == plain_toks and len(toks) == 4
        assert np.abs(kept - plain_kept).max() < 1e-4
        # the record says what the KERNEL spent: nothing where the
        # einsum took the bucket, else the tokens' pairs beside the
        # blocks' (one of 128 x 128 a head here: a toy bucket is
        # padded up to it)
        plain, forced = counts
        assert not any(key.startswith("flash_pairs") for key in plain)
        need, blocks = (forced[key] for key in ("flash_pairs_need",
                                                "flash_pairs_done"))
        assert 0 < need < blocks and blocks % (128 * 128) == 0
    return check

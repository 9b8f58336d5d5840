"""Training listeners — reference:
``org.deeplearning4j.optimize.api.TrainingListener`` SPI and impls
(ScoreIterationListener, PerformanceListener, CheckpointListener,
EvaluativeListener — SURVEY §5 metrics/observability).

The listener SPI is the universal hook point around the jitted train
step: iteration_done / on_epoch_start / on_epoch_end.
"""
from __future__ import annotations

import logging
import time
from pathlib import Path
from typing import Optional

logger = logging.getLogger("deeplearning4j_tpu")


class TrainingListener:
    def iteration_done(self, net, iteration: int, epoch: int):
        pass

    def reads_state(self, iteration: int) -> bool:
        """Whether ``iteration_done(net, iteration, ...)`` will read
        ``net.params``, ``net.opt_state`` or ``net.state`` as step
        ``iteration`` left them (to save or to evaluate them). A loop
        that launches the next step before it calls a step's listeners
        (``ParallelWrapper.fit``) asks every listener that has this
        method, and where one says yes lets no step run ahead of that
        one. A listener that reads the state without saying so may
        find it one step newer than ``iteration``."""
        return False

    def on_epoch_start(self, net):
        pass

    def on_epoch_end(self, net):
        pass


def _step_score(net) -> float:
    """The fit loop's already-computed step loss (``net.score_``) —
    listeners must never call ``net.score()`` per iteration: a
    dataset-scoring override would run an extra forward (device sync,
    possible retrace) just to log a number the step already produced."""
    score = getattr(net, "score_", None)
    return net.score() if score is None else score


class ScoreIterationListener(TrainingListener):
    """Logs score every N iterations (reference ScoreIterationListener)."""

    def __init__(self, print_iterations: int = 10):
        self.n = print_iterations

    def iteration_done(self, net, iteration, epoch):
        if iteration % self.n == 0:
            logger.info("Score at iteration %d is %s", iteration,
                        _step_score(net))


class PerformanceListener(TrainingListener):
    """Throughput/ETL timing (reference PerformanceListener)."""

    def __init__(self, frequency: int = 10, report=None,
                 iterator=None):
        """``iterator``: pass the AsyncDataSetIterator feeding fit() to
        include its cumulative ETL-wait in the report (the reference's
        ETL-time column)."""
        self.frequency = frequency
        self._last_time = None
        self._last_iter = None
        self.samples_per_sec = None
        self._report = report or (lambda msg: logger.info("%s", msg))
        self._batch = None
        self._iterator = iterator
        self._last_etl = 0.0

    def iteration_done(self, net, iteration, epoch):
        now = time.perf_counter()
        if self._last_time is not None and \
                iteration % self.frequency == 0:
            dt = now - self._last_time
            iters = iteration - self._last_iter
            if dt > 0 and iters > 0:
                msg = (f"iter {iteration}: {iters / dt:.1f} iter/sec, "
                       f"score {_step_score(net):.5f}")
                etl = getattr(self._iterator, "etl_wait_seconds", None)
                if etl is not None:
                    msg += (f", ETL wait "
                            f"{(etl - self._last_etl) * 1e3:.1f} ms")
                    self._last_etl = etl
                self._report(msg)
        if iteration % self.frequency == 0:
            self._last_time = now
            self._last_iter = iteration


class CheckpointListener(TrainingListener):
    """Periodic checkpoints with keep-last-K (reference
    CheckpointListener: every N iters/epochs, keepLast policies)."""

    def __init__(self, save_dir, save_every_n_iterations: Optional[int]
                 = None, save_every_n_epochs: Optional[int] = None,
                 keep_last: int = 3, sharded: bool = False):
        """``sharded=True`` switches from the zip ModelSerializer to the
        orbax-backed ShardedCheckpointer (async, tensorstore layout) —
        the multi-host/TP-sharded path; saves don't block the step."""
        self.dir = Path(save_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.every_iter = save_every_n_iterations
        self.every_epoch = save_every_n_epochs
        self.keep_last = keep_last
        self.sharded = sharded
        self._ck = None
        self._last_sharded_step = None

    def _save(self, net, tag: str):
        if self.sharded:
            if self._ck is None:
                from deeplearning4j_tpu.serialization import \
                    ShardedCheckpointer
                self._ck = ShardedCheckpointer(self.dir,
                                               keep_last=self.keep_last)
            # steps are net.iteration: an epoch-end save right after an
            # iteration-triggered one would collide — skip duplicates
            if net.iteration != self._last_sharded_step:
                self._ck.save(net.iteration, net)
                self._last_sharded_step = net.iteration
            return
        from deeplearning4j_tpu.serialization import ModelSerializer
        path = self.dir / f"checkpoint_{tag}.zip"
        ModelSerializer.write_model(net, path)
        ckpts = sorted(self.dir.glob("checkpoint_*.zip"),
                       key=lambda p: p.stat().st_mtime)
        for old in ckpts[:-self.keep_last]:
            old.unlink()
            # drop the CRC manifest sidecar with its checkpoint
            from deeplearning4j_tpu.resilience.checkpoint import \
                manifest_path
            manifest_path(old).unlink(missing_ok=True)

    def reads_state(self, iteration):
        return bool(self.every_iter) and iteration % self.every_iter == 0

    def iteration_done(self, net, iteration, epoch):
        if self.reads_state(iteration):
            self._save(net, f"iter_{iteration}")

    def on_epoch_end(self, net):
        if self.every_epoch and (net.epoch + 1) % self.every_epoch == 0:
            self._save(net, f"epoch_{net.epoch}")
        # epoch boundary = async barrier: surfaces any background save
        # error here instead of losing the checkpoint silently
        self.flush()

    def flush(self):
        """Block until pending async sharded saves land (call after a
        batch-API training loop that never crosses an epoch end)."""
        if self._ck is not None:
            self._ck.wait_until_finished()


class EvaluativeListener(TrainingListener):
    """Periodic eval during training (reference EvaluativeListener)."""

    def __init__(self, iterator, frequency_iters: int = 0,
                 frequency_epochs: int = 1, callback=None):
        self.iterator = iterator
        self.frequency_iters = frequency_iters
        self.frequency_epochs = frequency_epochs
        self.callback = callback or (
            lambda e: logger.info("\n%s", e.stats()))
        self.last_evaluation = None

    def _eval(self, net):
        e = net.evaluate(self.iterator)
        self.last_evaluation = e
        self.callback(e)

    def reads_state(self, iteration):
        return bool(self.frequency_iters) and \
            iteration % self.frequency_iters == 0

    def iteration_done(self, net, iteration, epoch):
        if self.reads_state(iteration):
            self._eval(net)

    def on_epoch_end(self, net):
        if self.frequency_epochs and \
                (net.epoch + 1) % self.frequency_epochs == 0:
            self._eval(net)


class CollectScoresListener(TrainingListener):
    """Collects (iteration, score) pairs (reference
    CollectScoresIterationListener)."""

    def __init__(self):
        self.scores = []

    def iteration_done(self, net, iteration, epoch):
        self.scores.append((iteration, _step_score(net)))

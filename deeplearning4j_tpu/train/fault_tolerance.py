"""Fault-tolerant training — checkpoint-based automatic restart.

Reference (SURVEY §5 "Failure detection / elastic recovery"): the
reference has no in-framework elasticity; its recovery story is
CheckpointListener + ModelSerializer resume, with Spark-level task
retry re-running failed partitions. On TPU the idiom is the same at
slice level: when a host/chip fails, the jax coordination service
tears the job down and the RESTARTED job resumes from the last
checkpoint. This module packages that idiom, hardened by the
resilience subsystem (ARCHITECTURE.md §10):

- in-process: ``FaultTolerantTrainer.fit`` retries around exceptions
  under a ``resilience.policy.RetryPolicy`` — exponential backoff with
  seeded jitter for transient errors (IO flakes, chip drops), at most
  ONE restore-and-retry for deterministic ones (shape/dtype/NaN —
  re-raised loudly instead of burning every restart), restoring the
  newest *valid* checkpoint (corrupt ones quarantined to ``corrupt/``).
- preemption: SIGTERM (the notice preemptible TPU slices get) is
  honored at the next iteration boundary — checkpoint, persist
  progress, return cleanly (exit code 0; the restarted job resumes).
- mid-epoch continuity: ``progress.json`` carries the iterator
  position (``batch_in_epoch``) alongside the counters, so a resumed
  run skips the batches the checkpoint already trained on and replays
  the exact uninterrupted trajectory (same per-iteration rng folds).
- cross-process: ``resume_or_init`` loads the newest valid checkpoint
  if one exists, so the training script is restart-idempotent (the
  reference's Spark-driver-resubmit pattern without Spark).
"""
from __future__ import annotations

import json
import logging
import time
from pathlib import Path
from typing import Callable, Optional

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.resilience import checkpoint as rck
from deeplearning4j_tpu.resilience.policy import (Preempted,
                                                  PreemptionHandler,
                                                  RetryPolicy, classify,
                                                  describe)

logger = logging.getLogger("deeplearning4j_tpu")


def newest_checkpoint(checkpoint_dir) -> Optional[Path]:
    """Newest *valid* checkpoint: candidates are verified (zip CRC
    sweep + required entries + manifest when present) newest-first;
    corrupt/partial files are quarantined to ``corrupt/`` with a
    warning instead of being handed to the restart loop."""
    return rck.newest_valid_checkpoint(checkpoint_dir)


def _restore_net(ckpt_path, template=None):
    """Restore the right network type for the checkpoint: from the
    template net when one is in hand, else from the checkpoint's own
    configuration.json (a ComputationGraph config carries node/input
    declarations; an MLN config carries a layer list)."""
    import json
    import zipfile
    from deeplearning4j_tpu.serialization import ModelSerializer
    if template is not None:
        is_graph = hasattr(template.conf, "inputs")
    else:
        with zipfile.ZipFile(ckpt_path) as zf:
            cj = json.loads(zf.read("configuration.json").decode())
        is_graph = "nodes" in cj
    if is_graph:
        return ModelSerializer.restore_computation_graph(str(ckpt_path))
    return ModelSerializer.restore_multi_layer_network(str(ckpt_path))


def read_progress(checkpoint_dir) -> dict:
    """``progress.json`` contents (``{}`` when absent/torn — a torn
    progress file must never block a restart)."""
    p = Path(checkpoint_dir) / "progress.json"
    try:
        return json.loads(p.read_text()) if p.exists() else {}
    except (OSError, ValueError):
        return {}


def resume_or_init(net_factory: Callable[[], "object"],
                   checkpoint_dir) -> "object":
    """Restart-idempotent bring-up: newest VALID checkpoint if present,
    else a fresh net from the factory (call this at the top of a
    training script; re-running the script after a slice restart — or
    a preemption — resumes)."""
    ckpt = newest_checkpoint(checkpoint_dir)
    if ckpt is not None:
        logger.info("resuming from %s", ckpt)
        net = _restore_net(ckpt)
        state = read_progress(checkpoint_dir)
        # fast-forward the epoch counter only when progress describes
        # THIS checkpoint (same iteration): a stale file — crash
        # between the checkpoint and progress writes, or a quarantined
        # newer checkpoint — must never desync counters from params
        if state.get("iteration") == net.iteration:
            net.epoch = max(net.epoch, state.get("epoch", net.epoch))
        return net
    return net_factory()


class _SkipBatches:
    """One-epoch iterator view that drops the first ``skip`` batches —
    resuming a mid-epoch restore at its persisted position so the
    replayed epoch matches the uninterrupted one batch-for-batch."""

    def __init__(self, base, skip: int):
        self.base = base
        self.skip = int(skip)

    def __len__(self):
        return max(0, len(self.base) - self.skip)

    def reset(self):
        if hasattr(self.base, "reset"):
            self.base.reset()

    def __iter__(self):
        it = iter(self.base)
        for _ in range(self.skip):
            try:
                next(it)
            except StopIteration:
                return
        yield from it


class _ProgressTracker:
    """Listener that (a) maintains the mid-epoch batch position, (b)
    persists ``progress.json`` at the checkpoint cadence, (c) turns a
    pending preemption notice into control flow at the iteration
    boundary — the only safe place to stop a train loop."""

    def __init__(self, trainer: "FaultTolerantTrainer"):
        self.trainer = trainer
        self._cur_epoch: Optional[int] = None
        self._epoch_start_iter = 0

    def reset_epoch_tracking(self):
        self._cur_epoch = None

    def reads_state(self, iteration):
        """The progress file describes the checkpoint cut at the same
        iteration: a loop that runs ahead lets no step pass it."""
        t = self.trainer
        return bool(t.every_iter) and iteration % t.every_iter == 0

    def iteration_done(self, net, iteration, epoch):
        t = self.trainer
        if self._cur_epoch != epoch:
            # first completed batch of this epoch (works for fit loops
            # without epoch hooks, e.g. ParallelWrapper)
            self._cur_epoch = epoch
            self._epoch_start_iter = iteration - 1
        t._batch_in_epoch = t._skip + (iteration - self._epoch_start_iter)
        if t.every_iter and iteration % t.every_iter == 0:
            t._save_progress()
        if t._preemption is not None and t._preemption.requested:
            raise Preempted()

    def on_epoch_start(self, net):
        pass

    def on_epoch_end(self, net):
        pass


class FaultTolerantTrainer:
    """fit() that survives mid-training failures by restoring the last
    valid checkpoint and continuing under a retry policy, and honors
    SIGTERM preemption by checkpointing and returning cleanly
    (reference analog: Spark task retry + CheckpointListener, SURVEY
    §5 — hardened per ARCHITECTURE.md §10).

    ``train_with``: optional trainer object whose ``fit(iterator,
    epochs=...)`` drives the epochs (e.g. a ``ParallelWrapper``);
    defaults to ``net`` itself. ``policy``: a
    ``resilience.policy.RetryPolicy`` (default: ``max_restarts``
    retries, 50 ms base backoff)."""

    def __init__(self, net, checkpoint_dir,
                 save_every_n_iterations: int = 50,
                 keep_last: int = 3, max_restarts: int = 3,
                 policy: Optional[RetryPolicy] = None,
                 handle_preemption: bool = True,
                 train_with=None):
        from deeplearning4j_tpu.train.listeners import CheckpointListener
        self.net = net
        self.dir = Path(checkpoint_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.max_restarts = max_restarts
        self.every_iter = save_every_n_iterations
        self.policy = policy or RetryPolicy(max_retries=max_restarts)
        self.handle_preemption = handle_preemption
        self.train_with = train_with
        self._listener = CheckpointListener(
            self.dir, save_every_n_iterations=save_every_n_iterations,
            keep_last=keep_last)
        self._keep_last = keep_last
        self._sharded = None        # lazy ShardedCheckpointer
        self._tracker = _ProgressTracker(self)
        self._preemption: Optional[PreemptionHandler] = None
        self._skip = 0              # batches to drop in the next epoch
        self._batch_in_epoch = 0    # live mid-epoch position
        self._det_restored = False  # deterministic error: one restore
        self.restarts = 0
        self.preempted = False

    def _save_progress(self):
        rck.atomic_write_bytes(self.dir / "progress.json", json.dumps(
            {"epoch": self.net.epoch,
             "iteration": self.net.iteration,
             "batch_in_epoch": self._batch_in_epoch,
             "time": time.time()}).encode())

    # -- ZeRO sharded-update integration (PR 5 x PR 3 interplay) --------
    def _sharded_wrapper(self):
        """The ``train_with`` wrapper when it carries its optimizer
        state as 1/N ZeRO shards — the case where the replicated zip
        path would have to materialize N× the live footprint just to
        stop cleanly."""
        tw = self.train_with
        return tw if tw is not None and \
            getattr(tw, "sharded_update", False) else None

    def _sharded_ck(self):
        if self._sharded is None:
            from deeplearning4j_tpu.serialization import \
                ShardedCheckpointer
            self._sharded = ShardedCheckpointer(
                self.dir / "sharded", keep_last=self._keep_last,
                async_save=False)
        return self._sharded

    def _newest_sharded_step(self) -> Optional[int]:
        if not (self.dir / "sharded").is_dir():
            return None
        steps = self._sharded_ck().all_steps()
        return max(steps) if steps else None

    def _restore_sharded(self, min_iteration: int = -1) -> bool:
        """Newest-valid sharded restore into the wrapper (quarantining
        corrupt step dirs, resharding onto the wrapper's world size if
        the checkpoint was written at a different one). Returns False
        when nothing restorable remains — OR when the step the
        fallback actually landed on is older than ``min_iteration``
        (the valid zip the caller holds): the newest SHARDED step
        being ahead of the zip says nothing until it verifies, so the
        comparison must be re-made after the fallback resolves and the
        caller must then restore its newer zip over this state."""
        tw = self._sharded_wrapper()
        try:
            self._sharded_ck().restore_latest_valid(wrapper=tw)
        except FileNotFoundError:
            return False
        if self.net.iteration < min_iteration:
            return False
        prog = read_progress(self.dir)
        if prog.get("iteration") == self.net.iteration:
            self.net.epoch = max(self.net.epoch,
                                 prog.get("epoch", self.net.epoch))
            self._skip = prog.get("batch_in_epoch", 0)
        else:
            self._skip = 0
        self._batch_in_epoch = self._skip
        self._tracker.reset_epoch_tracking()
        return True

    def _checkpoint_now(self):
        """Synchronous checkpoint + progress (preemption path). A
        ZeRO sharded-update wrapper publishes through
        ``ShardedCheckpointer.save_wrapper`` — each device writes only
        its 1/N optimizer shard — NOT the replicated zip path, whose
        gather would materialize exactly the N copies the sharded
        mode exists to avoid, in the narrow shutdown window a
        preemption notice leaves."""
        tw = self._sharded_wrapper()
        if tw is not None:
            ck = self._sharded_ck()
            if self.net.iteration not in ck.all_steps():
                # an existing step IS this iteration's state (e.g. a
                # second preemption before any progress) — orbax
                # refuses to overwrite, and there is nothing to add
                ck.save_wrapper(self.net.iteration, tw, wait=True)
            self._save_progress()
            return
        self._listener._save(self.net, f"iter_{self.net.iteration}")
        self._listener.flush()
        self._save_progress()

    @staticmethod
    def _zip_iteration(ckpt_path) -> int:
        """The iteration a zip checkpoint was cut at (its meta.json);
        -1 for anything unreadable — the caller treats it as older
        than any sharded step."""
        import zipfile
        try:
            with zipfile.ZipFile(ckpt_path) as zf:
                return int(json.loads(
                    zf.read("meta.json").decode()).get("iteration", -1))
        except Exception:
            return -1

    def _restore(self, e) -> None:
        """Restore the newest valid checkpoint into ``self.net`` (in
        place) and set the mid-epoch skip; no checkpoint → continue
        from in-memory params (the failed epoch restarts). When the
        trainer drives a ZeRO sharded-update wrapper, the newest
        checkpoint may be a SHARDED one (the preemption path writes
        those): the newer of the two chains wins, and the sharded
        restore reshards onto the current world size if it has to."""
        ckpt = newest_checkpoint(self.dir)
        if self._sharded_wrapper() is not None:
            sh_step = self._newest_sharded_step()
            zip_iter = self._zip_iteration(ckpt) if ckpt is not None \
                else -1
            if sh_step is not None and sh_step >= zip_iter:
                logger.warning(
                    "training failure (%s); restoring sharded "
                    "checkpoint step %d (restart %d/%d)", describe(e),
                    sh_step, self.restarts, self.max_restarts)
                # min_iteration: if the newest sharded steps turn out
                # corrupt and the fallback lands BELOW the valid zip,
                # fall through and let the zip restore win
                if self._restore_sharded(min_iteration=zip_iter):
                    return
        if ckpt is None:
            logger.warning(
                "failure before first checkpoint (%s); "
                "restarting epoch from in-memory params", e)
            self._skip = 0
            self._tracker.reset_epoch_tracking()
            return
        logger.warning("training failure (%s); restoring %s "
                       "(restart %d/%d)", describe(e), ckpt,
                       self.restarts, self.max_restarts)
        t0 = obs.now()
        restored = _restore_net(ckpt, template=self.net)
        net = self.net
        net.params = restored.params
        net.opt_state = restored.opt_state
        net.state = restored.state
        net.epoch = restored.epoch          # rewind counters to
        net.iteration = restored.iteration  # the checkpoint
        net._train_loop_fn = None     # re-jit with fresh buffers
        # resume at the persisted iterator position — only when the
        # progress file describes THIS checkpoint. The epoch max()
        # covers the boundary case: a checkpoint cut at an epoch's
        # last iteration carries the pre-increment epoch in its meta,
        # while progress (written at epoch end) has the completed one —
        # without it the whole epoch would be silently retrained.
        prog = read_progress(self.dir)
        if prog.get("iteration") == net.iteration:
            net.epoch = max(net.epoch, prog.get("epoch", net.epoch))
            self._skip = prog.get("batch_in_epoch", 0)
        else:
            self._skip = 0
        tw = self.train_with
        if tw is not None and getattr(tw, "_dp_state", None) is not None:
            # a ParallelWrapper's mode-specific device state (replica
            # params, residuals, in-flight queues) still reflects the
            # pre-failure run — drop it so _prepare() rebuilds it from
            # the RESTORED params; otherwise AVERAGING/ASYNC would keep
            # training un-restored replicas and _sync_back would
            # overwrite the restore at fit() end
            tw._dp_state = None
        if tw is not None and getattr(tw, "mode", None) in ("averaging",
                                                            "async"):
            # replica modes publish net.params only at _sync_back, so a
            # mid-epoch checkpoint holds epoch-START params: replay the
            # whole epoch instead of skipping batches those params
            # never trained on
            self._skip = 0
        self._batch_in_epoch = self._skip
        self._tracker.reset_epoch_tracking()
        if obs.trace.enabled():
            obs.trace.add_span("resilience/restore", t0, obs.now(),
                               args={"checkpoint": str(ckpt),
                                     "skip_batches": self._skip})

    def fit(self, iterator, epochs: int = 1):
        net = self.net
        trainer = self.train_with if self.train_with is not None else net
        for l in (self._listener, self._tracker):
            if l not in net.listeners:
                net.listeners.append(l)
        if self.handle_preemption and self._preemption is None:
            try:
                self._preemption = PreemptionHandler().install()
            except ValueError:      # not the main thread: poll-only
                self._preemption = None
        # sharded-chain resume: a preemption (or elastic departure)
        # under a ZeRO wrapper published 1/N shards, which the zip
        # scan of resume_or_init cannot see — restore them here when
        # they are newer than whatever the net already carries,
        # resharding onto the current topology if the world size
        # changed between the save and this restart
        if self._sharded_wrapper() is not None:
            sh_step = self._newest_sharded_step()
            if sh_step is not None and sh_step > net.iteration:
                logger.info("resuming from sharded checkpoint step %d",
                            sh_step)
                if not self._restore_sharded(
                        min_iteration=net.iteration):
                    # the sharded fallback landed on a step older than
                    # the state the net already carried (a zip-restored
                    # net, overwritten just now): put the newer zip
                    # state back
                    self._restore(RuntimeError(
                        "sharded chain fell back below the zip state"))
        # cross-process mid-epoch resume: a net brought up by
        # resume_or_init after a preemption/crash carries counters that
        # match progress.json — honor its batch_in_epoch so the resumed
        # epoch skips the batches the checkpoint already trained on.
        # Replica-state wrapper modes (averaging/async) are excluded:
        # they publish net.params only at _sync_back, so a mid-epoch
        # checkpoint holds epoch-START params and the epoch must replay
        # in full (same guard as _restore).
        if self._skip == 0 and net.iteration > 0 and \
                getattr(trainer, "mode", None) not in ("averaging",
                                                       "async"):
            prog = read_progress(self.dir)
            if prog.get("iteration") == net.iteration and \
                    prog.get("epoch", net.epoch) == net.epoch:
                self._skip = prog.get("batch_in_epoch", 0)
                self._batch_in_epoch = self._skip
        target_epoch = net.epoch + epochs
        try:
            while net.epoch < target_epoch:
                try:
                    it = _SkipBatches(iterator, self._skip) \
                        if self._skip else iterator
                    trainer.fit(it, epochs=1)
                    self._skip = 0
                    self._det_restored = False
                    self._batch_in_epoch = 0
                    self._save_progress()
                except Preempted:
                    self.preempted = True
                    obs.metrics.PREEMPTIONS.inc()
                    logger.warning(
                        "preemption: checkpointing at iteration %d and "
                        "stopping cleanly", net.iteration)
                    self._checkpoint_now()
                    break
                except KeyboardInterrupt:
                    raise
                except Exception as e:
                    kind = classify(e)
                    self.restarts += 1
                    obs.metrics.RESILIENCE_RESTARTS.inc()
                    if self.restarts > self.max_restarts:
                        raise RuntimeError(
                            f"training failed {self.restarts} times; "
                            f"last error: {e}") from e
                    if kind == "deterministic":
                        if self._det_restored:
                            raise   # one restore did not clear it
                        self._det_restored = True
                    else:
                        time.sleep(self.policy.delay(self.restarts))
                    self._restore(e)
        finally:
            if self._preemption is not None:
                self._preemption.uninstall()
                self._preemption = None
        return net

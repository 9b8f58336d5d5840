"""Model serialization — reference:
``org.deeplearning4j.util.ModelSerializer`` (zip of configuration.json +
coefficients.bin + updaterState.bin + normalizer.bin).

TPU-native format: a zip of
  configuration.json   — full MultiLayerConfiguration JSON
  params.npz           — one entry per param leaf (path-keyed). The
                         reference's single flattened coefficient buffer
                         deliberately does NOT carry over: sharded
                         checkpointing wants per-leaf arrays (SURVEY §5).
  state.npz            — non-trainable state (BN running stats, centers)
  updater.npz          — optax state leaves (resume-exact)
  normalizer.json      — optional fitted normalizer statistics
  meta.json            — iteration/epoch counters
"""
from __future__ import annotations

import io
import json
import logging
import os
import shutil
import zipfile
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np

from deeplearning4j_tpu.resilience import checkpoint as _ckpt
from deeplearning4j_tpu.resilience import faults as _faults

logger = logging.getLogger("deeplearning4j_tpu")


def _flatten_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(str(p) for p in path)
        out[key] = np.asarray(leaf)
    return out


def _writestr_det(zf: zipfile.ZipFile, name: str, data) -> None:
    """Deterministic zip entry: fixed DOS epoch timestamp so identical
    content always produces an identical archive (checksum-stable
    goldens; plain writestr stamps the current time)."""
    info = zipfile.ZipInfo(name, date_time=(1980, 1, 1, 0, 0, 0))
    info.compress_type = zipfile.ZIP_DEFLATED
    zf.writestr(info, data)


def _save_npz(zf: zipfile.ZipFile, name: str, tree) -> None:
    buf = io.BytesIO()
    np.savez(buf, **_flatten_with_paths(tree))
    _writestr_det(zf, name, buf.getvalue())


def _load_npz_into(zf: zipfile.ZipFile, name: str, tree):
    """Restore leaves into an existing pytree structure (template from a
    freshly init()ed model — mirrors the reference's approach of
    building the net from config then setting params)."""
    with zf.open(name) as f:
        data = np.load(io.BytesIO(f.read()))
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        leaves = []
        for path, leaf in flat:
            key = "/".join(str(p) for p in path)
            if key not in data:
                raise ValueError(f"checkpoint missing leaf {key}")
            import jax.numpy as jnp
            leaves.append(jnp.asarray(data[key]))
        return jax.tree_util.tree_unflatten(treedef, leaves)


class ModelSerializer:
    @staticmethod
    def write_model(net, path, save_updater: bool = True,
                    normalizer=None) -> None:
        """Atomic publication: the zip is assembled in a same-directory
        tmp file, fsync'd, and ``os.replace``d into place — a crash at
        any byte leaves either the previous complete checkpoint or the
        new complete checkpoint, never a truncated newest-by-mtime file
        for the restart loop to trip on (resilience/checkpoint.py)."""
        import zlib
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        _faults.inject("ckpt_write")
        meta = {"iteration": net.iteration, "epoch": net.epoch,
                "format_version": _ckpt.FORMAT_VERSION}
        # assemble the zip in memory (this is the single-host exchange
        # format — the GB-scale path is the orbax ShardedCheckpointer);
        # the buffer is what gets CRC'd for the manifest, so the file
        # is never re-read after its fsync
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as zf:
            _writestr_det(zf, "configuration.json", net.conf.to_json())
            _save_npz(zf, "params.npz", net.params)
            _save_npz(zf, "state.npz", net.state)
            if save_updater:
                opt_state = net.opt_state
                # a ZeRO sharded-update wrapper (parallel/wrapper.py)
                # carries the LIVE optimizer moments as 1/N shards;
                # net.opt_state is the stale init copy. Fold the
                # shards into the replicated layout for the zip —
                # export is the one place that materialization is the
                # point — so listener/trainer checkpoints taken during
                # sharded training stay resume-exact.
                wref = getattr(net, "_zero_wrapper", None)
                w = wref() if wref is not None else None
                if w is not None and w.sharded_update and \
                        w._dp_state is not None and \
                        opt_state is getattr(w, "_evicted_opt", None):
                    # identity check = ownership: anything else (a
                    # later replicated wrapper, direct net.fit, a
                    # restore) reassigns net.opt_state and thereby
                    # reclaims it from the sharded wrapper
                    opt_state = w.gather_opt_state()
                if opt_state is not None:
                    _save_npz(zf, "updater.npz", opt_state)
            if normalizer is not None:
                _writestr_det(zf, "normalizer.json",
                              json.dumps(normalizer.state_dict()))
            ishape = getattr(net, "_input_shape", None)
            if ishape:
                meta["input_shape"] = list(ishape)
            # ComputationGraph: persist per-input shapes so restore can
            # init() graphs built without input_types
            shapes = getattr(net, "_shapes", None)
            if shapes and hasattr(net.conf, "inputs"):
                meta["input_shapes"] = {
                    n: list(shapes[n]) for n in net.conf.inputs}
            _writestr_det(zf, "meta.json", json.dumps(meta))
        data = buf.getvalue()
        tmp = _ckpt.tmp_path_for(path)
        try:
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            _faults.inject("ckpt_commit")
            os.replace(tmp, path)
        except BaseException:
            tmp.unlink(missing_ok=True)
            raise
        _ckpt.fsync_dir(path.parent)
        # sidecar manifest (CRC32 + size + counters) AFTER the replace:
        # losing it to a crash only downgrades verification to the
        # zip-level checks
        _ckpt.write_manifest(path, {"iteration": net.iteration,
                                    "epoch": net.epoch},
                             crc32=zlib.crc32(data) & 0xFFFFFFFF)

    @staticmethod
    def _restore(zf: zipfile.ZipFile, net, meta: dict,
                 load_updater: bool):
        net.params = _load_npz_into(zf, "params.npz", net.params)
        net.state = _load_npz_into(zf, "state.npz", net.state)
        if load_updater and "updater.npz" in zf.namelist():
            net.opt_state = _load_npz_into(zf, "updater.npz",
                                           net.opt_state)
        net.iteration = meta.get("iteration", 0)
        net.epoch = meta.get("epoch", 0)
        return net

    @staticmethod
    def restore_multi_layer_network(path, load_updater: bool = True):
        from deeplearning4j_tpu.nn.config import MultiLayerConfiguration
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        with zipfile.ZipFile(Path(path)) as zf:
            conf = MultiLayerConfiguration.from_json(
                zf.read("configuration.json").decode())
            meta = json.loads(zf.read("meta.json").decode())
            net = MultiLayerNetwork(conf)
            ishape = tuple(meta.get("input_shape") or ()) or None
            net.init(input_shape=ishape)
            return ModelSerializer._restore(zf, net, meta, load_updater)

    @staticmethod
    def restore_computation_graph(path, load_updater: bool = True):
        from deeplearning4j_tpu.nn.graph import (
            ComputationGraph, ComputationGraphConfiguration)
        with zipfile.ZipFile(Path(path)) as zf:
            conf = ComputationGraphConfiguration.from_json(
                zf.read("configuration.json").decode())
            meta = json.loads(zf.read("meta.json").decode())
            net = ComputationGraph(conf)
            ishapes = meta.get("input_shapes")
            net.init(input_shapes={k: tuple(v)
                                   for k, v in ishapes.items()}
                     if ishapes else None)
            return ModelSerializer._restore(zf, net, meta, load_updater)

    @staticmethod
    def restore_normalizer(path):
        from deeplearning4j_tpu.data.normalizers import \
            normalizer_from_state
        with zipfile.ZipFile(Path(path)) as zf:
            if "normalizer.json" not in zf.namelist():
                return None
            return normalizer_from_state(
                json.loads(zf.read("normalizer.json").decode()))


class ShardedCheckpointer:
    """Orbax-backed sharded (optionally async) checkpointing for
    distributed training — the TPU-native checkpoint path (SURVEY §5:
    "orbax-style sharded async checkpoint of a params pytree + optax
    state; the flattened-single-buffer design does NOT carry over").

    Each host writes only its shards (tensorstore layout); restore
    honors a target sharding, so a TP/DP-sharded model round-trips
    without ever materialising full arrays on one host. Keep-last-K and
    step numbering mirror the reference CheckpointListener policies.

    The zip-based ``ModelSerializer`` remains the single-host exchange
    format; this is the scale path.
    """

    def __init__(self, directory, keep_last: int = 3,
                 async_save: bool = True):
        import orbax.checkpoint as ocp
        self._ocp = ocp
        self.directory = Path(directory).absolute()
        self.directory.mkdir(parents=True, exist_ok=True)
        self._keep_last = keep_last
        self._async_save = async_save
        self.mngr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep_last,
                enable_async_checkpointing=async_save))

    @staticmethod
    def _net_tree(net):
        """The one checkpoint structure (save and restore must agree)."""
        return {"params": net.params, "opt_state": net.opt_state,
                "state": net.state,
                "meta": {"iteration": net.iteration,
                         "epoch": net.epoch}}

    def save(self, step: int, net=None, *, tree=None, wait: bool = False):
        """Save a network's full training state (params + optimizer +
        layer state + counters) or an explicit pytree."""
        if tree is None:
            tree = self._net_tree(net)
        _faults.inject("ckpt_write")
        self.mngr.save(step, args=self._ocp.args.StandardSave(tree))
        if wait:
            self.mngr.wait_until_finished()
        return self

    def restore(self, step: Optional[int] = None, net=None, *,
                target=None):
        """Restore into ``net`` (in place) or return the raw tree.
        ``target``: a pytree of ShapeDtypeStruct/arrays (possibly with
        shardings) guiding placement; defaults to the net's current
        structure so shards land where the live arrays live."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(
                f"no checkpoints under {self.directory}")
        if net is not None and target is None:
            target = self._net_tree(net)
        args = (self._ocp.args.StandardRestore(target)
                if target is not None
                else self._ocp.args.StandardRestore())
        tree = self.mngr.restore(step, args=args)
        if net is not None:
            net.params = tree["params"]
            net.opt_state = tree["opt_state"]
            net.state = tree["state"]
            net.iteration = int(tree["meta"]["iteration"])
            net.epoch = int(tree["meta"]["epoch"])
            return net
        return tree

    # -- world manifests (elastic resharded restore) --------------------
    def _world_manifest_path(self, step: int) -> Path:
        return self.directory / f"world_{int(step)}.json"

    def world_manifest(self, step: int) -> Optional[dict]:
        """The sidecar written by :meth:`save_wrapper`: the world size
        (shard count) and optimizer layout the step was written under
        — what a restore onto a DIFFERENT world size gathers by."""
        try:
            return json.loads(self._world_manifest_path(step)
                              .read_text())
        except (OSError, ValueError):
            return None

    def save_wrapper(self, step: int, wrapper, *, wait: bool = False,
                     mesh_epoch: Optional[int] = None):
        """Checkpoint a ``ParallelWrapper``'s full training state —
        including the ZeRO sharded optimizer shards, which each device
        writes as its own 1/N (tensorstore layout): the replicated
        optimizer state is never materialized, not even to save. A
        ``world_<step>.json`` sidecar records the shard count and
        layout so a later restore onto M≠N devices knows how to
        gather and re-scatter (elastic fleets: hosts may die between
        save and restore). The manifest is published BEFORE the step
        itself: a crash in between leaves a manifest naming a step
        that never committed (harmless, pruned on the next save),
        while the reverse order would leave a committed step whose
        world size nobody can recover."""
        if jax.process_index() == 0:
            _ckpt.atomic_write_bytes(
                self._world_manifest_path(step),
                (json.dumps({
                    "step": int(step), "n_shards": int(wrapper.n),
                    "layout": ("zero-flat" if wrapper.sharded_update
                               else "replicated"),
                    "mesh_epoch": mesh_epoch}) + "\n").encode())
        self.save(step, tree=wrapper.checkpoint_tree(), wait=wait)
        if jax.process_index() == 0:
            # prune manifests whose step dirs keep-last already dropped
            steps = set(self.all_steps()) | {int(step)}
            for p in self.directory.glob("world_*.json"):
                try:
                    s = int(p.stem.split("_", 1)[1])
                except (IndexError, ValueError):
                    continue
                if s not in steps:
                    p.unlink(missing_ok=True)
        return self

    def restore_wrapper(self, wrapper, step: Optional[int] = None, *,
                        reshard: bool = True):
        """Restore a ``save_wrapper`` checkpoint into ``wrapper``.

        Same topology (checkpoint shard count == ``wrapper.n`` and
        same layout): the wrapper's live state tree (with its
        shardings) is the restore target, so ZeRO optimizer shards
        land directly back on their devices.

        Different topology (``reshard=True``, the default): the
        elastic-restore path — *gather by manifest, re-scatter by
        layout*. The ``world_<step>.json`` manifest names the source
        shard count N; a fully-replicated restore target is built
        analytically from the wrapper's own net (the padded flat
        shapes are a pure function of (params, N)), orbax gathers the
        saved shards into whole leaves, and
        ``ParallelWrapper.load_gathered_tree`` re-pads them through
        ``FlatShardLayout`` onto the surviving M devices — bit-exact
        on the real content (the zero pad is a training invariant;
        see ``parallel/zero.py::repad_flat_leaves``)."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(
                f"no checkpoints under {self.directory}")
        wm = self.world_manifest(step)
        want_layout = ("zero-flat" if wrapper.sharded_update
                       else "replicated")
        n_src = int(wm["n_shards"]) if wm else int(wrapper.n)
        src_layout = (wm or {}).get("layout", want_layout)
        if n_src == wrapper.n and src_layout == want_layout:
            target = wrapper.checkpoint_target()
            self._check_layout(step, target)
            tree = self.restore(step, target=target)
            wrapper.load_checkpoint_tree(tree)
            return wrapper
        if not reshard:
            raise ValueError(
                f"checkpoint step {step} was written at "
                f"{n_src} shards ({src_layout}) but the wrapper runs "
                f"{wrapper.n} ({want_layout}); pass reshard=True to "
                "gather and re-scatter")
        tree = self._restore_gathered(step, wrapper, n_src, src_layout)
        wrapper.load_gathered_tree(tree, src_layout)
        logger.warning(
            "resharded restore: step %d (%d shards, %s) -> %d shards",
            step, n_src, src_layout, wrapper.n)
        return wrapper

    def _restore_gathered(self, step: int, wrapper, n_src: int,
                          src_layout: str):
        """Gather-by-manifest: restore every leaf fully replicated on
        the wrapper's (new) mesh. The target is built analytically —
        params/state shapes from the live net, optimizer shapes from
        ``optimizer.init`` over the SOURCE flat layout — because the
        checkpoint's own sharding metadata names devices that no
        longer exist."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from deeplearning4j_tpu.parallel.zero import FlatShardLayout
        net = wrapper.net
        repl = NamedSharding(wrapper.mesh, P())

        def sds(leaf):
            return jax.ShapeDtypeStruct(tuple(leaf.shape), leaf.dtype,
                                        sharding=repl)

        if src_layout == "zero-flat":
            opt_ref = jax.eval_shape(
                lambda p: net._optimizer.init(
                    FlatShardLayout(p, n_src).flatten(p)), net.params)
        else:
            opt_ref = jax.eval_shape(net._optimizer.init, net.params)
        target = {
            "params": jax.tree.map(sds,
                                   jax.eval_shape(lambda: net.params)),
            "opt": jax.tree.map(sds, opt_ref),
            "state": jax.tree.map(sds,
                                  jax.eval_shape(lambda: net.state)),
            "meta": {"iteration": 0, "epoch": 0},
        }
        self._check_layout(step, target)
        return self.mngr.restore(
            step, args=self._ocp.args.StandardRestore(target))

    def _check_layout(self, step: int, target) -> None:
        """Hold the step's RECORDED leaf shapes (its metadata file: no
        array is read) against the restore target's, leaf by name: an
        intact record that names another shape was written by a
        different net, a configuration error (``LayoutMismatch``),
        never corruption. A record that cannot be read says nothing
        here: the restore itself then fails as corruption does."""
        from deeplearning4j_tpu.parallel.zero import LayoutMismatch
        try:
            recorded = {m.name: tuple(m.shape)
                        for m in jax.tree.leaves(
                            self.mngr.item_metadata(step))
                        if getattr(m, "shape", None) is not None}
        except Exception:           # unreadable record: not our call
            return

        def name(path):
            return ".".join(str(getattr(k, "key", getattr(
                k, "idx", getattr(k, "name", k)))) for k in path)

        for path, leaf in jax.tree_util.tree_flatten_with_path(target)[0]:
            want = tuple(getattr(leaf, "shape", ()))
            have = recorded.get(name(path), want)
            if have != want:
                raise LayoutMismatch(
                    f"checkpoint step {step} under {self.directory} "
                    f"records {name(path)} with shape {have}, but the "
                    f"net it is restored into wants {want}: this "
                    "directory was written by a different net")

    def restore_latest_valid(self, net=None, *, target=None,
                             wrapper=None):
        """Restore the newest step that actually restores, walking
        newest→oldest; an unrestorable (corrupt/partial) step dir is
        quarantined to ``corrupt/`` and the scan falls back — the
        sharded-path analog of
        ``resilience.checkpoint.newest_valid_checkpoint``. With
        ``wrapper=`` each candidate goes through
        :meth:`restore_wrapper` instead, so the fallback chain keeps
        its reshard-onto-M≠N capability: a corrupt newest written at
        8 devices quarantines, and the next-newest valid one still
        reshards onto the surviving 4."""
        from deeplearning4j_tpu.parallel.zero import LayoutMismatch
        last_err: Optional[Exception] = None
        while True:
            steps = sorted(self.all_steps(), reverse=True)
            if not steps:
                raise FileNotFoundError(
                    f"no restorable checkpoints under {self.directory}"
                ) from last_err
            step = steps[0]
            try:
                if wrapper is not None:
                    return self.restore_wrapper(wrapper, step)
                return self.restore(step, net=net, target=target)
            except (KeyboardInterrupt, SystemExit):
                raise
            except LayoutMismatch:
                # configuration error (wrong net for this checkpoint
                # dir), NOT corruption: fail fast — quarantining would
                # walk the chain and move aside every valid step
                raise
            except Exception as e:
                last_err = e
                logger.warning("sharded checkpoint step %d unrestorable "
                               "(%s); quarantining and falling back",
                               step, e)
                if not self._quarantine_step(step, str(e)):
                    # the corrupt step could not be moved aside (e.g.
                    # read-only mount): the next scan would retry the
                    # SAME step forever — fail loudly instead
                    raise

    def _quarantine_step(self, step: int, reason: str) -> bool:
        """Move a step dir to ``corrupt/``; returns False when nothing
        moved (caller must not loop on the same step)."""
        from deeplearning4j_tpu.resilience import checkpoint as _rck
        step_dir = self.directory / str(step)
        # the manager caches its step list (and may hold handles into
        # the dir): close, move, re-open
        self.mngr.close()
        if step_dir.is_dir():
            moved = _rck.quarantine(step_dir, reason) is not None
            if not moved and not step_dir.is_dir():
                # a concurrently-restoring peer won the move race —
                # the step is out of the scan either way
                moved = True
        else:
            # already moved aside (a peer, or a prior attempt): the
            # goal — this step out of every scan — is achieved
            moved = True
        if moved:
            # the world sidecar goes with its step (evidence stays
            # paired; a later save at the same step number must not
            # inherit a stale manifest)
            wm = self._world_manifest_path(step)
            if wm.is_file():
                try:
                    shutil.move(str(wm),
                                str(step_dir.parent / _rck.CORRUPT_DIR
                                    / wm.name))
                except OSError:
                    wm.unlink(missing_ok=True)
        self.mngr = self._ocp.CheckpointManager(
            self.directory,
            options=self._ocp.CheckpointManagerOptions(
                max_to_keep=self._keep_last,
                enable_async_checkpointing=self._async_save))
        return moved

    def latest_step(self) -> Optional[int]:
        return self.mngr.latest_step()

    def all_steps(self):
        return sorted(self.mngr.all_steps())

    def wait_until_finished(self):
        self.mngr.wait_until_finished()

    def close(self):
        self.mngr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

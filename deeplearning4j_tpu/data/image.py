"""Image ETL — loader, record reader, augmentation transforms.

Reference: ``datavec-data-image`` (SURVEY §2.4):
``org.datavec.image.loader.NativeImageLoader`` (JavaCV decode +
resize), ``org.datavec.image.recordreader.ImageRecordReader`` with
``ParentPathLabelGenerator``, and ``org.datavec.image.transform.*``
(Crop/Flip/Rotate/Resize/Scale/ColorConversion/Pipeline image
transforms) — the ImageNet input pipeline.

TPU-native design: decode/augment stay on host (cv2/PIL — exactly the
reference's JavaCV role); the output is NHWC float32 batches, the
layout TPU convolutions prefer (the reference emits NCHW for cuDNN).
Batches then stream through AsyncDataSetIterator's native ring queue to
overlap ETL with device compute.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

from deeplearning4j_tpu.data.records import RecordReader


def _cv2():
    import cv2
    return cv2


class NativeImageLoader:
    """Decode + resize to fixed [H, W, C] float32 (reference
    NativeImageLoader(height, width, channels); ``channels_first``
    opts into the reference's NCHW layout)."""

    def __init__(self, height: int, width: int, channels: int = 3,
                 channels_first: bool = False):
        self.height, self.width = height, width
        self.channels = channels
        self.channels_first = channels_first

    def _decode(self, src) -> np.ndarray:
        cv2 = _cv2()
        if isinstance(src, (str, os.PathLike)):
            flag = (cv2.IMREAD_GRAYSCALE if self.channels == 1
                    else cv2.IMREAD_COLOR)
            img = cv2.imread(str(src), flag)
            if img is None:
                raise IOError(f"cannot decode image: {src}")
            if self.channels == 3:
                img = cv2.cvtColor(img, cv2.COLOR_BGR2RGB)
        else:
            img = np.asarray(src)
        if img.ndim == 2:
            img = img[..., None]
        if img.shape[-1] != self.channels:
            if self.channels == 1:
                img = cv2.cvtColor(img, cv2.COLOR_RGB2GRAY)[..., None]
            elif self.channels == 3 and img.shape[-1] == 1:
                img = np.repeat(img, 3, axis=-1)
            else:
                raise ValueError(
                    f"cannot convert {img.shape[-1]} channels to "
                    f"{self.channels}")
        return img

    def as_matrix(self, src) -> np.ndarray:
        """One image → [1, H, W, C] (or [1, C, H, W]) float32."""
        x = self.load(src)[None]
        return x

    def load(self, src) -> np.ndarray:
        cv2 = _cv2()
        img = self._decode(src)
        if img.shape[:2] != (self.height, self.width):
            img = cv2.resize(img, (self.width, self.height),
                             interpolation=cv2.INTER_AREA)
            if img.ndim == 2:
                img = img[..., None]
        out = img.astype(np.float32)
        if self.channels_first:
            out = np.transpose(out, (2, 0, 1))
        return out


# ---------------------------------------------------------------------------
# Transforms (reference org.datavec.image.transform.ImageTransform SPI)
# ---------------------------------------------------------------------------

class ImageTransform:
    """Base augmentation op: HWC uint8/float in, HWC out. Random
    transforms draw from the generator passed to ``transform`` so a
    pipeline's sampling is reproducible."""

    def transform(self, img: np.ndarray, rng=None) -> np.ndarray:
        raise NotImplementedError

    def __call__(self, img, rng=None):
        return self.transform(
            img, rng if rng is not None else np.random.default_rng())


class ResizeImageTransform(ImageTransform):
    def __init__(self, width: int, height: int):
        self.width, self.height = width, height

    def transform(self, img, rng=None):
        cv2 = _cv2()
        out = cv2.resize(img, (self.width, self.height),
                         interpolation=cv2.INTER_AREA)
        return out[..., None] if out.ndim == 2 else out


class ScaleImageTransform(ImageTransform):
    """Random uniform rescale by ±delta (reference
    ScaleImageTransform(delta))."""

    def __init__(self, delta: float):
        self.delta = delta

    def transform(self, img, rng=None):
        cv2 = _cv2()
        s = 1.0 + float(rng.uniform(-self.delta, self.delta))
        h, w = img.shape[:2]
        out = cv2.resize(img, (max(1, int(w * s)), max(1, int(h * s))),
                         interpolation=cv2.INTER_LINEAR)
        return out[..., None] if out.ndim == 2 else out


class CropImageTransform(ImageTransform):
    """Random crop up to crop_{top,left,bottom,right} pixels
    (reference CropImageTransform)."""

    def __init__(self, crop: int):
        self.crop = crop

    def transform(self, img, rng=None):
        h, w = img.shape[:2]
        t = int(rng.integers(0, self.crop + 1))
        l = int(rng.integers(0, self.crop + 1))
        b = int(rng.integers(0, self.crop + 1))
        r = int(rng.integers(0, self.crop + 1))
        return img[t:h - b if b else h, l:w - r if r else w]


class FlipImageTransform(ImageTransform):
    """mode: 0 vertical, 1 horizontal, -1 both, None random choice
    (reference FlipImageTransform's OpenCV flip codes)."""

    def __init__(self, mode: Optional[int] = None):
        self.mode = mode

    def transform(self, img, rng=None):
        mode = (self.mode if self.mode is not None
                else int(rng.integers(-1, 2)))
        cv2 = _cv2()
        out = cv2.flip(img, mode)
        return out[..., None] if out.ndim == 2 else out


class RotateImageTransform(ImageTransform):
    """Random rotation in ±angle degrees about the center (reference
    RotateImageTransform)."""

    def __init__(self, angle: float):
        self.angle = angle

    def transform(self, img, rng=None):
        cv2 = _cv2()
        a = float(rng.uniform(-self.angle, self.angle))
        h, w = img.shape[:2]
        m = cv2.getRotationMatrix2D((w / 2, h / 2), a, 1.0)
        out = cv2.warpAffine(img, m, (w, h))
        return out[..., None] if out.ndim == 2 else out


class ColorConversionTransform(ImageTransform):
    """Color-space conversion by cv2 code name, e.g. 'RGB2GRAY',
    'RGB2HSV' (reference ColorConversionTransform wraps cvtColor)."""

    def __init__(self, code: str):
        self.code = code

    def transform(self, img, rng=None):
        cv2 = _cv2()
        out = cv2.cvtColor(img, getattr(cv2, f"COLOR_{self.code}"))
        return out[..., None] if out.ndim == 2 else out


class EqualizeHistTransform(ImageTransform):
    """Histogram equalization per channel (reference
    EqualizeHistTransform)."""

    def transform(self, img, rng=None):
        cv2 = _cv2()
        u8 = img.astype(np.uint8)
        chans = [cv2.equalizeHist(u8[..., c])
                 for c in range(u8.shape[-1])]
        return np.stack(chans, axis=-1)


class PipelineImageTransform(ImageTransform):
    """Sequential pipeline; each stage applies with probability p
    (reference PipelineImageTransform(List<Pair<transform, prob>>))."""

    def __init__(self, steps: Sequence[Union[ImageTransform,
                                             Tuple[ImageTransform,
                                                   float]]],
                 shuffle: bool = False):
        self.steps = [(s, 1.0) if isinstance(s, ImageTransform) else s
                      for s in steps]
        self.shuffle = shuffle

    def transform(self, img, rng=None):
        steps = list(self.steps)
        if self.shuffle:
            rng.shuffle(steps)
        for t, p in steps:
            if p >= 1.0 or rng.random() < p:
                img = t.transform(img, rng)
        return img


# ---------------------------------------------------------------------------
# Record reader
# ---------------------------------------------------------------------------

class ParentPathLabelGenerator:
    """Label = parent directory name (reference
    ParentPathLabelGenerator)."""

    def get_label(self, path: str) -> str:
        return Path(path).parent.name


class ImageRecordReader(RecordReader):
    """Walks a directory tree of images; each record is
    ``[image_array, label_index]`` (reference ImageRecordReader yields
    [NDArrayWritable, IntWritable]). Labels discovered from parent dirs
    (sorted, stable) unless an explicit list is given."""

    EXTS = {".png", ".jpg", ".jpeg", ".bmp", ".ppm", ".pgm"}

    def __init__(self, height: int, width: int, channels: int = 3,
                 label_generator=None,
                 labels: Optional[List[str]] = None,
                 transform: Optional[ImageTransform] = None,
                 channels_first: bool = False, seed: int = 0,
                 workers: int = 0):
        self.loader = NativeImageLoader(height, width, channels,
                                        channels_first)
        self.label_generator = label_generator \
            or ParentPathLabelGenerator()
        self.labels = list(labels) if labels else None
        self.transform = transform
        self.seed = seed
        #: decode/augment parallelism: >1 maps the per-file work over
        #: a thread pool (cv2 releases the GIL, so this scales on
        #: multi-core hosts — an earlier ETL sizing put ~10 cores
        #: per v5e chip at full ResNet-50 rate; not re-measured), with
        #: bounded read-ahead and ORDERED yield. Augmentation rng is
        #: per-file (seeded by (seed, epoch, index)) so output is
        #: deterministic regardless of thread timing while each epoch
        #: still draws fresh augments.
        self.workers = workers
        self._rng = np.random.default_rng(seed)
        self._epoch = 0
        self._files: List[str] = []
        self._pool = None          # one executor per reader (lazy)
        self._inflight: set = set()

    def initialize(self, root: str) -> "ImageRecordReader":
        """Scan root/<label>/ for images (reference
        initialize(FileSplit))."""
        files = sorted(
            str(p) for p in Path(root).rglob("*")
            if p.suffix.lower() in self.EXTS)
        if not files:
            raise FileNotFoundError(f"no images under {root}")
        self._files = files
        if self.labels is None:
            self.labels = sorted(
                {self.label_generator.get_label(f) for f in files})
        return self

    def num_labels(self) -> int:
        return len(self.labels or [])

    def _load(self, f: str, rng) -> list:
        """Per-file decode → augment → resize → label (shared by the
        sequential and thread-pool paths)."""
        img = self.loader._decode(f)
        if self.transform is not None:
            img = self.transform.transform(img, rng)
        cv2 = _cv2()
        if img.shape[:2] != (self.loader.height, self.loader.width):
            img = cv2.resize(
                img, (self.loader.width, self.loader.height),
                interpolation=cv2.INTER_AREA)
            if img.ndim == 2:
                img = img[..., None]
        x = img.astype(np.float32)
        if self.loader.channels_first:
            x = np.transpose(x, (2, 0, 1))
        lab = self.labels.index(self.label_generator.get_label(f))
        return [x, lab]

    def _executor(self):
        """ONE pool per reader, not per epoch: a training run iterates
        this reader epochs×, and thread create/teardown per ``__iter__``
        is pure churn (plus a warm pool keeps cv2's per-thread state
        hot). Lazy so workers<=1 readers never spin threads."""
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(self.workers)
        return self._pool

    def __iter__(self):
        if self.workers and self.workers > 1:
            # ordered parallel decode with a bounded in-flight window
            # (2× workers) so memory stays O(workers), not O(dataset).
            # Augment rng is keyed (seed, epoch, index): deterministic
            # under any thread timing, but fresh per epoch like the
            # sequential stream
            from collections import deque

            epoch = self._epoch
            self._epoch += 1

            def task(i, f):
                return self._load(
                    f, np.random.default_rng([self.seed, epoch, i]))

            ex = self._executor()
            window: deque = deque()
            try:
                for i, f in enumerate(self._files):
                    fut = ex.submit(task, i, f)
                    window.append(fut)
                    self._inflight.add(fut)
                    # self-prune on completion (late-bound so close()
                    # can swap the set out from under old epochs): an
                    # abandoned epoch must not pin decoded arrays in
                    # _inflight for the reader's lifetime
                    fut.add_done_callback(
                        lambda f: self._inflight.discard(f))
                    if len(window) >= 2 * self.workers:
                        yield window.popleft().result()
                while window:
                    yield window.popleft().result()
            finally:
                # a consumer abandoning the generator mid-epoch must
                # not leave a dead epoch decoding: cancel what hasn't
                # started (running decodes finish into _inflight and
                # are joined by close()); the pool itself stays up for
                # the next epoch
                for fut in window:
                    fut.cancel()
            return
        for f in self._files:
            yield self._load(f, self._rng)

    def reset(self):
        pass

    def close(self):
        """Join in-flight decode futures and tear the pool down — an
        abandoned partial epoch must not keep worker threads churning
        past the reader's lifetime. Idempotent; the reader is reusable
        after close (the pool respawns lazily)."""
        import concurrent.futures
        # swap first: done-callbacks resolve self._inflight late, so
        # they prune the fresh set; drain the old one with atomic
        # pop()s — a straggler callback may still hold a reference to
        # it, and list(set) can blow up mid-iteration on a concurrent
        # discard
        inflight, self._inflight = self._inflight, set()
        futs = []
        while inflight:
            try:
                futs.append(inflight.pop())
            except KeyError:
                break
        for fut in futs:
            fut.cancel()
        if futs:
            concurrent.futures.wait(futs)
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class BatchImageETL:
    """Batched decode-to-device ETL tail (reference NativeImageLoader +
    ImagePreProcessingScaler fused): decoded u8 [N,H,W,C] pixels →
    normalized f32 NHWC with per-image random crop + horizontal flip.
    The per-pixel loop runs in the threaded native runtime
    (native/dl4j_tpu_native.cpp img_batch_normalize_u8) when available,
    with an identical numpy fallback."""

    def __init__(self, out_hw=None, mean=None, std=None,
                 random_crop: bool = False, random_flip: bool = False,
                 seed: int = 0, n_threads: int = 0):
        self.out_hw = out_hw
        self.mean = mean
        self.std = std
        self.random_crop = random_crop
        self.random_flip = random_flip
        self.n_threads = n_threads
        self._rng = np.random.default_rng(seed)

    def __call__(self, batch_u8: np.ndarray,
                 train: bool = True) -> np.ndarray:
        from deeplearning4j_tpu import native
        n, h, w, _ = batch_u8.shape
        oh, ow = self.out_hw or (h, w)
        crops = flips = None
        if train and self.random_crop and (oh < h or ow < w):
            crops = np.stack(
                [self._rng.integers(0, h - oh + 1, n),
                 self._rng.integers(0, w - ow + 1, n)], 1)
        elif oh < h or ow < w:           # eval: center crop
            crops = np.tile([[(h - oh) // 2, (w - ow) // 2]], (n, 1))
        if train and self.random_flip:
            flips = self._rng.integers(0, 2, n).astype(np.uint8)
        return native.img_batch_normalize(
            batch_u8, out_hw=(oh, ow), mean=self.mean, std=self.std,
            crop_offsets=crops, flips=flips, n_threads=self.n_threads)

"""Training traffic: a seeded pool of host batches, cycled.

What an input pipeline hands to ``fit``: float32 NHWC images and
one-hot labels in host memory, decode excluded, the host-to-device
hand-off included. Parameters (the workload file's ``traffic.params``):
``batch``, ``pool`` (distinct batches), ``batches_per_call`` (how many
one ``fit`` call consumes). Every row of every batch differs.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np


class CycledBatches:
    """A finite iterator of ``(features, labels)`` over the pool,
    taking up where the last one ended; ``fit`` consumes one whole."""

    def __init__(self, pool, count: int, start: int, annotate):
        self._pool, self._count, self._at = pool, count, start
        self._annotate = annotate

    def __iter__(self):
        return self

    def __next__(self):
        if self._count <= 0:
            raise StopIteration
        with self._annotate("stage-batch"):
            self._count -= 1
            item = self._pool[self._at % len(self._pool)]
            self._at += 1
            return item


def make_pool(params: dict, config: dict, seed: int):
    """The pool's batches, each from a generator of its own spawned
    from the seed, drawn side by side (numpy drops the interpreter
    lock while it draws: 1.2 GB in a second, not four)."""
    size, classes = config["image_size"], config["num_classes"]
    eye = np.eye(classes, dtype=np.float32)

    def batch(child):
        rng = np.random.default_rng(child)
        return (rng.standard_normal(
                    (params["batch"], size, size, config["num_channels"]),
                    dtype=np.float32),
                eye[rng.integers(0, classes, params["batch"])])

    with ThreadPoolExecutor(max_workers=params["pool"]) as pool:
        return list(pool.map(
            batch, np.random.SeedSequence(seed).spawn(params["pool"])))

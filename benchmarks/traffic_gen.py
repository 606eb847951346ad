"""The one general generator of training traffic: a pool of distinct host
batches, made from the seed, whose shapes come from the cell's traffic file
(``batch``, ``seq``, ``pool``) and the configuration's ``inputs``.

Every seed gives the same sizes in the same order; only the values differ.
A batch is a ``(features, labels)`` pair of numpy arrays on the host: the
copy to the device is the program's work, inside the window.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def _features(rng, spec: dict, batch: int, traffic: dict):
    if spec["kind"] == "image":
        # random bytes through a 256-entry table: a quarter of the random
        # draws of a float32 normal, and no float pass over the batch
        dtype = np.dtype(getattr(ml_dtypes, spec["dtype"], None) or spec["dtype"])
        table = ((np.arange(256, dtype=np.float32) - 127.5) / 73.9).astype(dtype)
        return table[rng.integers(0, 256, (batch, *spec["shape"]), dtype=np.uint8)]
    if spec["kind"] == "tokens":
        return rng.integers(0, spec["vocab"], (batch, traffic["seq"]), dtype=np.int32)
    raise ValueError(f"unknown feature kind {spec['kind']!r}")


def _labels(rng, spec: dict, batch: int):
    if spec["kind"] == "onehot":
        out = np.zeros((batch, spec["classes"]), np.float32)
        out[np.arange(batch), rng.integers(0, spec["classes"], batch)] = 1.0
        return out
    raise ValueError(f"unknown label kind {spec['kind']!r}")


def make_pool(inputs: dict, traffic: dict, seed: int) -> list:
    """``traffic['pool']`` batches of ``traffic['batch']`` rows that all differ."""
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    batch = traffic["batch"]
    return [(_features(rng, inputs["features"], batch, traffic),
             _labels(rng, inputs["labels"], batch))
            for _ in range(traffic["pool"])]

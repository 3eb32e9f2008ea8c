"""The one traffic generator: turns a cell's traffic file and ``--seed``
into the inputs a run sends.

A traffic file (``bench/traffic/<traffic>.json``) is data: the loop
kind, sizes and the number of answers the correctness check samples.
Every seed gets the same sizes in the same order; the seed changes the
token ids, features and weights (and which answers are checked), so two
seeds ask for the same work.
"""
from __future__ import annotations

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of one seed.  Seeds are any
    non-negative integer, including ones beyond 32 bits."""
    return np.random.default_rng([int(seed), *stream])


def jax_seed(seed: int, stream: int) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` drawn from the seed."""
    return int(rng(seed, stream).integers(0, 2**31 - 1))


def prompts(traffic: dict, seed: int, vocab: int) -> list[np.ndarray]:
    """Closed-batch loop: ``pool`` distinct ``(batch, length)`` token
    batches; call ``i`` of the window sends batch ``i % pool``."""
    r = rng(seed, 1)
    shape = (int(traffic["batch"]), int(traffic["length"]))
    return [r.integers(0, vocab, size=shape, dtype=np.int32)
            for _ in range(int(traffic["pool"]))]

"""The one traffic generator: each training step's batch from the seed.

A traffic file gives the sizes (``seq``, ``batch``) and how the program runs
them (``chunks``, ``offload``, ``remat``, ``mesh``).  Tokens are drawn
uniformly over the configuration's vocabulary from (seed, step), so every row
of every step differs and a seed always gives the same batches; labels are
the tokens shifted by one.  Every seed draws the same sizes.
"""
from __future__ import annotations

import numpy as np

BATCH_STREAM = 1


def batch(traffic: dict, vocab_size: int, seed: int, step: int) -> dict:
    """{"tokens", "labels"}: int32 [batch, seq] for step ``step`` (0-based)."""
    b, s = traffic["batch"], traffic["seq"]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), BATCH_STREAM, step]))
    toks = rng.integers(0, vocab_size, size=(b, s + 1), dtype=np.int32)
    return {"tokens": toks[:, :s], "labels": toks[:, 1:]}

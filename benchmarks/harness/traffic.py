"""The one traffic generator: a traffic file's parameters and the seed in,
the inputs of one unit of work out. The program sees only what this makes.

Every unit of a cell has the same size, so every seed gives the same work in
another order (other tokens, another device masked)."""

from __future__ import annotations

import numpy as np


def _rng(seed: int, unit: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(unit)])


def token_batch(traffic: dict, vocab: int, seed: int, unit: int):
    """``(tokens, labels)``, each (batch, seq_len) int32, labels the next
    token. ``copy_half``: the second half of a row repeats its first half, so
    the back half is learnable only by attending seq_len / 2 tokens back. All
    rows differ."""
    batch, seq = int(traffic["batch"]), int(traffic["seq_len"])
    rng = _rng(seed, unit)
    if traffic["tokens"] != "copy_half" or seq % 2:
        raise ValueError(
            f"token pattern {traffic['tokens']!r} at seq_len {seq}: only "
            "copy_half at an even length is generated"
        )
    first = rng.integers(0, vocab, size=(batch, seq // 2 + 1))
    rows = np.concatenate([first, first[:, 1:]], axis=1)
    return rows[:, :-1].astype(np.int32), rows[:, 1:].astype(np.int32)


def contributor_mask(traffic: dict, n: int, seed: int, unit: int) -> np.ndarray:
    """(n,) float32 of 0/1 with ``masked_per_round`` zeros at seeded places."""
    mask = np.ones((n,), np.float32)
    k = int(traffic["masked_per_round"])
    if k:
        mask[_rng(seed, unit).choice(n, size=k, replace=False)] = 0.0
    return mask


def probe_indices(traffic: dict, size: int, seed: int) -> np.ndarray:
    """Element positions whose answers are kept from every round."""
    k = int(traffic["probe_elements"])
    idx = _rng(seed, 2**31 - 1).integers(0, size, size=k, dtype=np.int64)
    idx[0], idx[-1] = 0, size - 1  # both ends always
    return idx.astype(np.uint32)

"""The one traffic generator: inputs drawn from a seed and a cell's
traffic parameters (``bench/traffic/<name>.json``).

Every draw is a pure function of (stream seed, ids), so the program and the
plain reference see the same rows without either handing them to the
other, and every seed gives the same shapes: only the token ids and labels
change with the seed, never the amount of work.
"""
from __future__ import annotations

import numpy as np

TRAIN, TEST, PROMPT = 0, 1, 2


def _rng(seed: int, *ids: int) -> np.random.Generator:
    return np.random.default_rng([seed, *ids])


def train_batches(seed: int, cid: int, rnd: int, *, steps: int, batch: int,
                  seq: int, vocab: int, n_labels: int):
    """A client's local-step batches for one round: tokens
    (steps, batch, seq) and labels (steps, batch), int32."""
    g = _rng(seed, TRAIN, cid, rnd)
    toks = g.integers(0, vocab, (steps, batch, seq), dtype=np.int32)
    labels = g.integers(0, n_labels, (steps, batch), dtype=np.int32)
    return toks, labels


def test_rows(seed: int, cid: int, *, rows: int, seq: int, vocab: int,
              n_labels: int):
    """A client's held-out eval rows: tokens (rows, seq), labels (rows,)."""
    g = _rng(seed, TEST, cid)
    toks = g.integers(0, vocab, (rows, seq), dtype=np.int32)
    labels = g.integers(0, n_labels, (rows,), dtype=np.int32)
    return toks, labels


def prompts(seed: int, index: int, *, batch: int, length: int, vocab: int):
    """The ``index``-th batch of prompts: (batch, length) int32."""
    return _rng(seed, PROMPT, index).integers(0, vocab, (batch, length),
                                              dtype=np.int32)

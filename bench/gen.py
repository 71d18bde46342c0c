"""Inputs of a traffic mix (a JSON file under ``bench/traffic/``), rebuilt
from the run's seed for the reference.

A ``train`` mix feeds the program through its own ``TokenPipeline``
(``data/pipeline.py``, synthetic source), as the training launcher does.
The reference may import nothing of the program, so it rebuilds the same
rows here: a copy of the pipeline's stream, a pure function of (seed,
step, row).  Each row is Markov-ish (a token mostly follows from the
previous one and a per-row theme, 15% of tokens are drawn at random), so
the loss has structure to learn; every step's rows differ, and the same
seed gives the same rows.
"""
from __future__ import annotations

import hashlib

import numpy as np


def _row_rng(seed: int, step: int, row: int) -> np.random.Generator:
    h = hashlib.blake2b(f"{seed}:{step}:{row}".encode(), digest_size=8).digest()
    return np.random.default_rng(int.from_bytes(h, "little"))


def train_row(seed: int, step: int, row: int, seq: int, vocab: int,
              structure: int) -> np.ndarray:
    r = _row_rng(seed, step, row)
    theme = int(r.integers(0, structure))
    first = int(r.integers(0, vocab))
    jump = r.random(seq) < 0.15
    rand = r.integers(0, vocab, seq)
    toks = np.empty(seq, np.int32)
    prev = toks[0] = first
    for t in range(1, seq):
        prev = toks[t] = (rand[t] if jump[t]
                          else (prev * 31 + theme * 7 + 13) % vocab)
    return toks


def train_rows(seed: int, step: int, batch: int, seq: int, vocab: int,
               structure: int) -> np.ndarray:
    """[batch, seq] int32 rows of one step."""
    return np.stack([train_row(seed, step, b, seq, vocab, structure)
                     for b in range(batch)])

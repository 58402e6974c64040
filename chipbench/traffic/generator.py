"""The one generator of training traffic: a batch of token rows per step,
made on the host from ``--seed`` and the step's index, as an input
pipeline hands a trainer its next batch.

A traffic file (``traffic/<name>.json``) fixes the job: workers, rows
per worker and sequence length. Every seed gets the same sizes; only the
tokens differ. The rows of every step differ
from those of every other step.

The token rule follows ``repro.data.synthetic.lm_batch_for`` (uniform ids
over the vocabulary), drawn with numpy on the host instead of on the
device, with each label the next token of the same row.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def rows(job) -> int:
    return int(job["batch_per_worker"]) * int(job["workers"])


def tokens_per_step(job) -> int:
    return rows(job) * int(job["seq_len"])


def batch(job, vocab: int, seed: int, step: int) -> Dict[str, np.ndarray]:
    """The global batch of step ``step``: ``tokens`` and ``labels``, each
    ``(workers * batch_per_worker, seq_len)`` int32; worker ``i`` holds
    rows ``[i*B, (i+1)*B)``."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed % 2**64, 1, int(step)]))
    ids = rng.integers(0, vocab, (rows(job), int(job["seq_len"]) + 1),
                       dtype=np.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}

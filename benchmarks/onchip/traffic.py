"""The benchmark's one traffic generator: a pool of training batches
drawn from a seed, with the parameters of a traffic file.

``zipf_repeat`` is a copy of the program's ``repro.data.SyntheticLM``
stream (Zipf unigram over the vocabulary, each token repeating the
previous one with probability ``repeat_p``, padded tail, batch sorted by
length), kept here so that the yardstick cannot move with the program.
One change: lengths are a stratified draw of the uniform distribution
on ``[min_len_frac * S, S]`` and are the same in every batch, so every
seed and every batch holds the same number of tokens; the seed changes
which tokens, not how much work.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

GENERATORS = ("zipf_repeat",)
KEYS = ("generator", "zipf_a", "repeat_p", "min_len_frac", "seq_len",
        "global_batch", "pool")


def load(path: Path) -> dict:
    """Read and check a traffic file."""
    t = json.loads(Path(path).read_text())
    missing = [k for k in KEYS if k not in t]
    if missing:
        raise ValueError(f"{path}: traffic file lacks {missing}")
    if t["generator"] not in GENERATORS:
        raise ValueError(f"{path}: unknown generator {t['generator']!r}")
    if not (t["seq_len"] >= 2 and t["global_batch"] >= 1 and t["pool"] >= 3
            and 0.0 < t["min_len_frac"] <= 1.0
            and 0.0 <= t["repeat_p"] < 1.0 and t["zipf_a"] >= 0.0):
        raise ValueError(f"{path}: parameter out of range: {t}")
    return t


def lengths(t: dict) -> np.ndarray:
    """The sorted sequence lengths of every batch: the midpoints of
    ``global_batch`` equal strata of ``[min_len_frac * S, S]``."""
    S, B = t["seq_len"], t["global_batch"]
    lo = max(2, int(t["min_len_frac"] * S))
    width = S + 1 - lo
    return (lo + ((np.arange(B) + 0.5) * width / B).astype(np.int64)
            ).astype(np.int32)


def label_tokens(t: dict) -> int:
    """Non-padding label tokens in one batch."""
    return int(lengths(t).sum())


def make_pool(t: dict, vocab_size: int,
              seed: int) -> List[Dict[str, np.ndarray]]:
    """``t["pool"]`` batches of ``tokens``/``labels`` [B, S] int32 and
    ``seq_len`` [B] int32; labels are -1 and tokens 0 past the length."""
    S, B = t["seq_len"], t["global_batch"]
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    unigram = ranks ** (-float(t["zipf_a"]))
    unigram /= unigram.sum()
    lens = lengths(t)
    pos = np.arange(S)[None, :]
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(t["pool"]):
        toks = rng.choice(vocab_size, size=(B, S + 1), p=unigram
                          ).astype(np.int32)
        rep = rng.random((B, S + 1)) < t["repeat_p"]
        for s in range(1, S + 1):
            toks[:, s] = np.where(rep[:, s], toks[:, s - 1], toks[:, s])
        tokens = toks[:, :S].copy()
        labels = toks[:, 1:].copy()
        labels[pos >= lens[:, None]] = -1
        tokens[pos >= lens[:, None]] = 0
        pool.append({"tokens": tokens, "labels": labels,
                     "seq_len": lens.copy()})
    return pool

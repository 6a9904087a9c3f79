"""Packed next-token batches for causal language-model training: token ids
drawn Zipf over the vocabulary (rank r with weight 1 / (r + 1)^``zipf_s``)
through a seeded permutation of the ids, so that a few ids are very common
and most are rare, as in text, and a router's expert loads come out
uneven. Every sequence is ``seq_len`` tokens (packed, no padding, so a
token is a token); the labels are the next token (``seq_len + 1`` are
drawn a sequence). Drawn from ``numpy.random.default_rng(seed)``."""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np


def generate(params: Mapping[str, Any], seed: int, *, vocab: int,
             batches: int, batch: int) -> Dict[str, Any]:
    rng = np.random.default_rng(seed)
    ids_of_rank = rng.permutation(vocab).astype(np.int32)
    weight = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** float(
        params["zipf_s"])
    cdf = np.cumsum(weight / weight.sum())
    shape = (batches, batch, int(params["seq_len"]) + 1)
    rank = np.minimum(np.searchsorted(cdf, rng.random(shape)), vocab - 1)
    toks = ids_of_rank[rank]
    return {"ids": np.ascontiguousarray(toks[..., :-1]),
            "labels": np.ascontiguousarray(toks[..., 1:])}

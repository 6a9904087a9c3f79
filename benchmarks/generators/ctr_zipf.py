"""Slot-tagged CTR traffic with a hot set: the one generator every
``ctr_zipf`` traffic file parameterises.

Keys are ``slot << 32 | id``. Each slot has its own pool of ``pool_per_slot``
ids; a batch position draws a RANK from Zipf(``zipf_s``) over the pool (the
key draw of ``tools/make_anchor_v2.py``: p(rank r) ~ r^-s; ``zipf_s`` = 0 is
uniform) and a seeded permutation per slot maps ranks to ids, so hot ids
are scattered over the id space as hashed features are. Batches therefore
carry duplicates and a hot set. Dense features are N(0,1); the label is a
noisy threshold on two of them (``click_threshold`` sets the click rate),
so the tower has something to learn and the loss can be seen to fall.

Everything is drawn vectorised from ``numpy.random.default_rng(seed)``:
the same seed gives the same bytes.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np


def generate(params: Mapping[str, Any], seed: int, *, slots: int, dense: int,
             pool_per_slot: int, batches: int, batch: int) -> Dict[str, Any]:
    """``pool``: every key a batch can hold, [slots * pool_per_slot] u64 (the
    pass's working set); ``lo32`` [batches, batch, slots] u32 (the low key
    halves the trainers ship), ``dense`` [batches, batch, dense] f32,
    ``labels`` [batches, batch] i8."""
    rng = np.random.default_rng(seed)
    s = float(params["zipf_s"])
    n = batches * batch
    if s > 0.0:
        p = 1.0 / np.arange(1, pool_per_slot + 1, dtype=np.float64) ** s
        cdf = np.cumsum(p)
        cdf /= cdf[-1]
        ranks = np.searchsorted(cdf, rng.random((n, slots)), side="right")
        ranks = np.minimum(ranks, pool_per_slot - 1)
    else:
        ranks = rng.integers(0, pool_per_slot, size=(n, slots))
    ids = np.empty((n, slots), np.uint32)
    for c in range(slots):
        perm = rng.permutation(pool_per_slot).astype(np.uint32)
        ids[:, c] = perm[ranks[:, c]]
    x = rng.normal(size=(n, dense)).astype(np.float32)
    score = x[:, 0] + 0.5 * x[:, min(1, dense - 1)]
    labels = (score + 0.3 * rng.normal(size=n)
              > float(params["click_threshold"])).astype(np.int8)
    slot_hi = np.arange(slots, dtype=np.uint64) << np.uint64(32)
    pool = (np.arange(pool_per_slot, dtype=np.uint64)[None, :]
            + slot_hi[:, None]).reshape(-1)
    return {"pool": pool,
            "lo32": ids.reshape(batches, batch, slots),
            "dense": x.reshape(batches, batch, dense),
            "labels": labels.reshape(batches, batch)}

"""Fixed-length token batches for masked-language-model style training:
ids and labels uniform over the vocabulary, every sequence ``seq_len`` long
(no padding, so a token is a token). Drawn from
``numpy.random.default_rng(seed)``."""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np


def generate(params: Mapping[str, Any], seed: int, *, vocab: int,
             batches: int, batch: int) -> Dict[str, Any]:
    rng = np.random.default_rng(seed)
    shape = (batches, batch, int(params["seq_len"]))
    return {"ids": rng.integers(0, vocab, shape, dtype=np.int32),
            "labels": rng.integers(0, vocab, shape, dtype=np.int32)}

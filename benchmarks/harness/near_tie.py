"""How far a float32 step may differ from its reference at a router's
near-tie: ONE rule for the three references that excuse one
(``configs/olmoe-1b-7b``, ``joyai-llm-flash``, ``lfm2-8b-a1b``). They load
this file by its path (``.reference.py`` files are themselves loaded by
path, from places that have no ``harness`` to import). Host numpy on arrays
the check already has; imports nothing of the program.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def readings(select: np.ndarray, gap: np.ndarray, same: np.ndarray,
             index: np.ndarray, within: float) -> Dict[str, Any]:
    """``select`` [layers, T, E]: what the REFERENCE's router ranks experts
    by (a probability; a score plus its bias); ``gap`` [layers, T]: its
    k-th less its (k+1)-th; ``same`` [layers, T]: whether the system's set
    of experts equals the reference's own; ``index`` [layers, T, k]: the
    system's experts. A (layer, token) is clear where ``gap > within``.

    ``topk_match_where_clear``: the share of clear ones whose sets are
    equal (1.0 when none is clear). ``near_ties_resolved_differently``:
    how many of the others differ. ``near_tie_excess``: over those, the
    largest ``select`` OUTSIDE the system's set less the least INSIDE it
    — the other expert of an exact tie reads 0.0, an expert within the
    tie at most ``within``, any other the distance to it; 0.0 when none
    differs."""
    clear = gap > within
    flipped = ~same & ~clear
    chosen = np.zeros(select.shape, bool)
    np.put_along_axis(chosen, np.asarray(index), True, axis=-1)
    excess = (np.max(np.where(chosen, -np.inf, select), axis=-1)
              - np.min(np.where(chosen, select, np.inf), axis=-1))
    return {"clear_tokens_share": float(np.mean(clear)),
            "topk_match_where_clear": float(np.mean(same[clear]))
            if clear.any() else 1.0,
            "near_ties_resolved_differently": int(np.sum(flipped)),
            "near_tie_excess": float(np.max(excess[flipped]))
            if flipped.any() else 0.0}

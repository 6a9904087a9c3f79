"""The near-tie excuse in the reference that ranks by its router's logits
themselves (``configs/smallthinker-21b-a3b.reference.py``, PR 44: a softmax
router with no bias) — host arithmetic alone, beside
``test_near_tie_checks.py``, which holds the three references that rank by a
probability or by a score plus its bias. One rule for the four:
``harness/near_tie.readings``.
"""

import importlib.util
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _module(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("fault", ["none", "other_expert_of_the_tie",
                                   "expert_outside_the_tie",
                                   "expert_at_a_clear_token"])
def test_near_tie_excess_by_logits(fault):
    """``compare_routing(..., "f32")`` on arrays made here. One token's 6th
    and 7th logit are equal bit for bit; the system may take either, and
    nothing else — neither an expert outside the tie there, nor another
    expert at a token whose 6th and 7th are clear."""
    reference = _module("_ref_smallthinker", "configs",
                        "smallthinker-21b-a3b.reference.py")
    k, token, clear = 6, 9, 20
    logits = np.random.default_rng(5).normal(0.0, 0.02, (2, 64, 64))
    order = np.argsort(-logits[0, token])
    kth, nxt, last = order[k - 1], order[k], order[-1]
    logits[0, token, nxt] = logits[0, token, kth]
    ranked = np.sort(logits, axis=-1)[..., ::-1]
    own = np.argsort(-logits, axis=-1, kind="stable")[..., :k]
    own[0, token][own[0, token] == nxt] = kth      # of the tie: this one
    ref = {"router_logits": logits, "own_index": own,
           "gap": ranked[..., k - 1] - ranked[..., k]}
    gap = reference.TOL["f32"]["gap"]
    assert ref["gap"][0, token] == 0.0 and ref["gap"][0, clear] > gap
    index = own.copy()
    if fault == "expert_at_a_clear_token":
        index[0, clear, k - 1] = np.argsort(-logits[0, clear])[k]
    elif fault != "none":
        index[0, token][list(index[0, token]).index(kth)] = (
            nxt if fault == "other_expert_of_the_tie" else last)
    out = reference.compare_routing(
        {"router_logits": logits, "expert_index": index}, ref, "f32")
    assert out["tol"] == {"logit_abs": reference.TOL["f32"]["logit_abs"],
                          "topk_match_where_clear": 1.0,
                          "near_tie_excess": gap}
    assert out["logit_abs"] == 0.0
    if fault == "expert_at_a_clear_token":
        assert out["topk_match_where_clear"] < 1.0 and not out["ok"]
        assert out["near_ties_resolved_differently"] == 0
        return
    assert out["topk_match_where_clear"] == 1.0
    assert out["near_ties_resolved_differently"] == int(fault != "none")
    if fault == "expert_outside_the_tie":
        assert out["near_tie_excess"] > 1000 * gap and not out["ok"]
    else:
        assert out["near_tie_excess"] == 0.0 and out["ok"]

"""Operations a sparse-expert causal decoder needs, from shapes alone.

As ``harness/flops.py``: only what the forward and backward passes
REQUIRE (nothing recomputed; gathers, sorts, norms, rotary, softmax and
the top-k count as zero), a matmul of [m, k] x [k, n] is 2*m*k*n, backward
is twice forward."""

from __future__ import annotations

from typing import Mapping


def expert_matmul_flops_per_token(cfg: Mapping[str, int]) -> float:
    """Forward + backward FLOPs a token of ONE expert layer's grouped
    matmuls: each of the ``num_experts_per_tok`` assignments meets three
    matrices of hidden x expert width (gate, up, down), three passes."""
    return 3.0 * 3 * 2 * cfg["hidden_size"] * cfg["intermediate_size"] \
        * cfg["num_experts_per_tok"]


def causal_moe_train_flops_per_token(cfg: Mapping[str, int],
                                     seq: int) -> float:
    """Forward + backward FLOPs per token of an OLMoE-style decoder with
    an untied full-vocabulary head, causal attention over ``seq`` packed
    positions.

    Per layer and token, forward: q, k, v and output projections
    4 * 2*h*h; causal scores and weighted values 2*h*(seq+1) together
    (position t attends to t+1 keys, (seq+1)/2 on average, 2*h a key for
    the scores and the same for the values); the router 2*h*E; the
    experts ``expert_matmul_flops_per_token`` / 3. Once per token: the
    head 2*h*vocab."""
    h = cfg["hidden_size"]
    per_layer = (4 * 2 * h * h + 2 * h * (seq + 1)
                 + 2 * h * cfg["num_experts"]
                 + expert_matmul_flops_per_token(cfg) / 3.0)
    forward = cfg["num_hidden_layers"] * per_layer + 2 * h * cfg["vocab_size"]
    return 3.0 * forward

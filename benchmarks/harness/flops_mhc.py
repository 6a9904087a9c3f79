"""Operations a hyper-connected latent-attention expert decoder needs
(``xing4.0-29b-a4b``), from shapes alone.

As ``harness/flops_mla.py``, whose counts of the latent projections, the
causal scores and values and a SwiGLU these are: only what the forward and
backward passes REQUIRE — a matmul of [m, k] x [k, n] is 2*m*k*n, backward
is twice forward; nothing recomputed (``recompute: blocks`` rebuilds every
block: time, never work) or padded; gathers, sorts, norms, rotary, softmax,
the top-k, the sigmoids and the twenty Sinkhorn steps (elementwise on
[n, n] a token) count as zero. The residual path's own matmuls DO count:
the mappings' projection [nC] x [nC, 2n + n^2], the collect [n] x [n, C]
and the scatter [n, n + 1] x [n + 1, C] of every sublayer. No prediction
module."""

from __future__ import annotations

from typing import Dict, Mapping, Optional

from harness import flops_mla


def residual_path_flops_per_token(cfg: Mapping[str, int]) -> float:
    """Forward FLOPs a token of ONE sublayer's residual path."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return 2.0 * (n * c * (2 * n + n * n) + n * c + n * (n + 1) * c)


def block_flops_per_token(cfg: Mapping[str, int], seq: int,
                          held_per_token: Optional[float] = None
                          ) -> Dict[str, float]:
    """Forward FLOPs a token by part: ``attention`` (projections, scores
    and values) and ``residual_path`` (two sublayers) of any block,
    ``dense_ffn`` of a leading dense block, ``expert_ffn`` of an expert
    block (the router at its published width, the shared expert, and
    ``held_per_token`` assignments a token on held experts — None: even
    routing, ``num_experts_per_tok`` x held / router width), ``head``."""
    h = cfg["hidden_size"]
    if held_per_token is None:
        held_per_token = cfg["num_experts_per_tok"] * flops_mla.held_share(cfg)
    expert = flops_mla.swiglu_flops(h, cfg["moe_intermediate_size"])
    return {
        "attention": flops_mla.mla_projection_flops_per_token(cfg)
        + flops_mla.attention_core_flops_per_token(cfg, seq),
        "residual_path": 2.0 * residual_path_flops_per_token(cfg),
        "dense_ffn": flops_mla.swiglu_flops(h, cfg["intermediate_size"]),
        "expert_ffn": 2.0 * h * cfg["router_width"]
        + (cfg["n_shared_experts"] + held_per_token) * expert,
        "head": 2.0 * h * cfg["vocab_size"]}


def forward_flops_per_token(cfg: Mapping[str, int], seq: int,
                            held_per_token: Optional[float] = None) -> float:
    """``first_k_dense_replace`` dense blocks, the expert blocks, the head
    over the held vocabulary. ``held_per_token``: the mean over the expert
    layers of a token's assignments that landed on a held expert, as the
    step routed (None: even routing)."""
    part = block_flops_per_token(cfg, seq, held_per_token)
    dense = cfg["first_k_dense_replace"]
    both = part["attention"] + part["residual_path"]
    return (dense * (both + part["dense_ffn"])
            + (cfg["num_hidden_layers"] - dense) * (both + part["expert_ffn"])
            + part["head"])


def train_flops_per_token(cfg: Mapping[str, int], seq: int,
                          held_per_token: Optional[float] = None) -> float:
    """Forward + backward (three passes); recomputation is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq, held_per_token)

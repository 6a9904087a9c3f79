"""Operations a conv-hybrid expert decoder needs (``lfm2-8b-a1b``), from
shapes alone.

As ``harness/flops.py``: only what the forward and backward passes
REQUIRE — a matmul of [m, k] x [k, n] is 2*m*k*n, backward is twice
forward; nothing recomputed or padded; gathers, sorts, norms, rotary,
softmax, the top-k, the repeat of k and v, and the convolution's gates
and taps (7 element-wise operations a channel against 16,384 in its two
projections) count as zero."""

from __future__ import annotations

from typing import Dict, List, Mapping


def layer_kinds(cfg: Mapping[str, int]) -> List[str]:
    """The mixers of the layers the configuration runs."""
    first = cfg.get("first_layer", 0)
    return list(cfg["layer_types"][first:first + cfg["num_hidden_layers"]])


def conv_blocks(cfg: Mapping[str, int]) -> int:
    return layer_kinds(cfg).count("conv")


def conv_operator_flops_per_token(cfg: Mapping[str, int]) -> float:
    """Forward FLOPs a token of one gated short convolution: W_in (hidden
    -> 3 hidden) and W_out (hidden -> hidden)."""
    h = cfg["hidden_size"]
    return 2.0 * (h * 3 * h + h * h)


def conv_operator_flops(cfg: Mapping[str, int], tokens: float) -> float:
    """Forward + backward FLOPs of ``tokens`` through every conv block's
    operator (three passes)."""
    return 3.0 * tokens * conv_blocks(cfg) * conv_operator_flops_per_token(cfg)


def attention_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Forward FLOPs a token of one grouped-query attention mixer: W_q and
    W_o at ``num_attention_heads`` x head, W_k and W_v at
    ``num_key_value_heads`` x head, and the causal scores and weighted
    values of every QUERY head (position t attends to t+1 keys,
    (seq+1)/2 on average; the repeat of k and v is no operation)."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    d = h // H
    proj = 2.0 * (2 * h * H * d + 2 * h * cfg["num_key_value_heads"] * d)
    return proj + 2.0 * H * (d + d) * (seq + 1) / 2.0


def swiglu_flops(hidden: int, width: int) -> float:
    """Forward FLOPs of one row through gate, up and down."""
    return 3.0 * 2 * hidden * width


def held_share(cfg: Mapping[str, int]) -> float:
    """Share of a token's assignments that land on a held expert when
    loads are even: held / router width."""
    return cfg["num_experts"] / cfg["router_width"]


def train_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Forward + backward FLOPs per token of the configuration as this
    chip runs it: each layer's mixer, ``num_dense_layers`` dense
    feed-forwards, in every other layer the router at its published width
    and the HELD share of the ``num_experts_per_tok`` assignments, and
    the tied head over the held vocabulary."""
    h = cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    convs = kinds.count("conv")
    expert_layers = len(kinds) - cfg["num_dense_layers"]
    forward = (convs * conv_operator_flops_per_token(cfg)
               + (len(kinds) - convs) * attention_flops_per_token(cfg, seq)
               + cfg["num_dense_layers"] * swiglu_flops(
                   h, cfg["intermediate_size"])
               + expert_layers * (
                   2.0 * h * cfg["router_width"]
                   + cfg["num_experts_per_tok"] * held_share(cfg)
                   * swiglu_flops(h, cfg["moe_intermediate_size"]))
               + 2.0 * h * cfg["vocab_size"])
    return 3.0 * forward


def held_expert_flops(cfg: Mapping[str, int], assignments: float) -> float:
    """Forward + backward FLOPs of ``assignments`` rows through a held
    expert's three matrices (three passes)."""
    return 3.0 * assignments * swiglu_flops(cfg["hidden_size"],
                                            cfg["moe_intermediate_size"])


#: matmuls of each flash kernel, each 2 * d FLOP a (query, key) pair a head
FLASH_MATMULS = {"flash_fwd": 2,        # q k^T, p v
                 "flash_bwd_dq": 3,     # q k^T, do v^T, ds k
                 "flash_bwd_dkv": 4}    # q k^T, p^T do, do v^T, ds^T q


def flash_kernel_floor(kernel: str, cfg: Mapping[str, int], batch: int,
                       seq: int, peaks: Mapping[str, float],
                       operand_bytes: int = 2, result_bytes: int = 4
                       ) -> Dict[str, float]:
    """One causal call of a flash kernel at this configuration's grouped-
    query widths, as ``harness/flops_mla.flash_kernel_floor`` counts the
    latent ones: ``flop`` over the L(L+1)/2 pairs a QUERY head the mask
    leaves, at the head's 64 (not the 128 lanes it is padded to);
    ``bytes`` with q, dO, o and dq at ``num_attention_heads`` and k, v, dk
    and dv at ``num_key_value_heads`` (the repeat to 32 heads is the
    program's, not the model's), operands once in the kernels' multiply
    dtype, results once in the caller's, the row statistics one float32 a
    query row (forward: lse out; backward: lse and delta in);
    ``floor_s`` = the larger of flop / peak FLOP/s and bytes / peak
    bytes/s."""
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // H
    flop = 2.0 * d * FLASH_MATMULS[kernel] * batch * H * seq * (seq + 1) / 2.0
    q_rows, kv_rows = batch * H * seq, batch * G * seq
    reads = (q_rows + 2 * kv_rows) * d * operand_bytes          # q, k, v
    if kernel == "flash_fwd":
        moved = reads + q_rows * d * result_bytes + q_rows * 4
    else:
        reads += q_rows * d * operand_bytes + q_rows * 8   # dO, lse, delta
        wrote = q_rows if kernel == "flash_bwd_dq" else 2 * kv_rows
        moved = reads + wrote * d * result_bytes
    return {"flop": flop, "bytes": float(moved),
            "floor_s": max(flop / peaks["bf16_flops"],
                           moved / peaks["hbm_bytes_per_s"])}

"""Operations and bytes a latent-attention expert decoder needs
(``joyai-llm-flash``), from shapes alone.

As ``harness/flops.py``: only what the forward and backward passes
REQUIRE — a matmul of [m, k] x [k, n] is 2*m*k*n, backward is twice
forward; nothing recomputed, no padding (q.k at 192, not the 256 lanes
it occupies), gathers, sorts, norms, rotary, softmax and the top-k zero.
The kernels' floors count a kernel's own matmuls (the backward kernels
rebuild the scores from q, k and the saved statistics: that is the
algorithm, not a recomputation of the program's) over the positions the
causal mask leaves — L(L+1)/2 a head, not whole blocks — and each operand
and result once at its logical width."""

from __future__ import annotations

from typing import Dict, Mapping

#: matmuls of each flash kernel as (those q.k wide, those v wide)
KERNEL_MATMULS = {"flash_fwd": (1, 1),        # q k^T | p v
                  "flash_bwd_dq": (2, 1),     # q k^T, ds k | do v^T
                  "flash_bwd_dkv": (2, 2)}    # q k^T, ds^T q | p^T do, do v^T


def attention_blocks(cfg: Mapping[str, int]) -> int:
    """Blocks with an attention sublayer: every layer and each prediction
    module's."""
    return cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]


def expert_blocks(cfg: Mapping[str, int]) -> int:
    return attention_blocks(cfg) - cfg["first_k_dense_replace"]


def mla_projection_flops_per_token(cfg: Mapping[str, int]) -> float:
    """Forward FLOPs a token of one block's five latent-attention
    matrices."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * (h * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * qk
                  + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                  + cfg["kv_lora_rank"] * H * (cfg["qk_nope_head_dim"]
                                               + cfg["v_head_dim"])
                  + H * cfg["v_head_dim"] * h)


def attention_core_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Forward FLOPs a token of one block's causal scores and weighted
    values: position t attends to t+1 keys, (seq+1)/2 on average."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return 2.0 * cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) \
        * (seq + 1) / 2.0


def swiglu_flops(hidden: int, width: int) -> float:
    """Forward FLOPs of one row through gate, up and down."""
    return 3.0 * 2 * hidden * width


def held_share(cfg: Mapping[str, int]) -> float:
    """Share of a token's assignments that land on a held expert when
    loads are even: held / router width."""
    return cfg["n_routed_experts"] / cfg["router_width"]


def train_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Forward + backward FLOPs per token of the configuration as this
    chip runs it: ``first_k_dense_replace`` dense blocks, the expert
    blocks (the prediction module's among them) with the router at its
    published width, the shared expert and the HELD share of the
    ``num_experts_per_tok`` assignments, the module's projection, and the
    head twice (main and module) over the held vocabulary."""
    h = cfg["hidden_size"]
    attn = mla_projection_flops_per_token(cfg) \
        + attention_core_flops_per_token(cfg, seq)
    expert = swiglu_flops(h, cfg["moe_intermediate_size"])
    expert_block = (attn + 2.0 * h * cfg["router_width"]
                    + cfg["n_shared_experts"] * expert
                    + cfg["num_experts_per_tok"] * held_share(cfg) * expert)
    dense_block = attn + swiglu_flops(h, cfg["intermediate_size"])
    mtp = cfg["num_nextn_predict_layers"]
    forward = (cfg["first_k_dense_replace"] * dense_block
               + expert_blocks(cfg) * expert_block
               + mtp * 2.0 * (2 * h) * h
               + (1 + mtp) * 2.0 * h * cfg["vocab_size"])
    return 3.0 * forward


def held_expert_flops(cfg: Mapping[str, int], assignments: float) -> float:
    """Forward + backward FLOPs of ``assignments`` rows through a held
    expert's three matrices (three passes)."""
    return 3.0 * assignments * swiglu_flops(cfg["hidden_size"],
                                            cfg["moe_intermediate_size"])


def flash_kernel_floor(kernel: str, cfg: Mapping[str, int], batch: int,
                       seq: int, peaks: Mapping[str, float],
                       operand_bytes: int = 2, result_bytes: int = 4,
                       causal: bool = True) -> Dict[str, float]:
    """One call of a flash kernel at this configuration's widths:
    ``flop`` and ``bytes`` required, and ``floor_s`` = the larger of flop
    / peak FLOP/s and bytes / peak bytes/s. Operands (q, k, v, and dO in
    the backward) are read once in the kernels' multiply dtype, results
    (o; dq; dk, dv) written once in the caller's, the row statistics one
    float32 a row (forward: lse out; backward: lse and delta in)."""
    H = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    n_qk, n_v = KERNEL_MATMULS[kernel]
    pairs = seq * (seq + 1) / 2.0 if causal else float(seq * seq)
    flop = 2.0 * batch * H * pairs * (n_qk * qk + n_v * dv)
    rows = batch * H * seq
    reads = rows * (2 * qk + dv) * operand_bytes          # q, k, v
    if kernel == "flash_fwd":
        moved = reads + rows * dv * result_bytes + rows * 4
    else:
        reads += rows * dv * operand_bytes + rows * 8      # dO, lse, delta
        wrote = qk if kernel == "flash_bwd_dq" else qk + dv
        moved = reads + rows * wrote * result_bytes
    return {"flop": flop, "bytes": float(moved),
            "floor_s": max(flop / peaks["bf16_flops"],
                           moved / peaks["hbm_bytes_per_s"])}

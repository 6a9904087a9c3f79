"""Operations a sliding-window expert decoder needs
(``smallthinker-21b-a3b``), from shapes alone.

As ``harness/flops.py``: only what the forward and backward passes
REQUIRE — a matmul of [m, k] x [k, n] is 2*m*k*n, backward is twice
forward; nothing recomputed or padded; gathers, sorts, norms, rotary,
softmax, the top-k and the repeat of k and v count as zero. A windowed
layer needs the (query, key) pairs its band LEAVES, whatever the program
that implements the window walks."""

from __future__ import annotations

from typing import Dict, List, Mapping, Optional


def layer_windows(cfg: Mapping[str, int]) -> List[Optional[int]]:
    """The window (None: every earlier key) of each layer the
    configuration runs."""
    first = cfg.get("first_layer", 0)
    rows = cfg["sliding_window_layout"][
        first:first + cfg["num_hidden_layers"]]
    return [cfg["sliding_window_size"] if w else None for w in rows]


def attended_pairs(seq: int, window: Optional[int]) -> float:
    """(query, key) pairs one head's mask leaves of a ``seq``-long
    sequence: query i sees min(i + 1, window) keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def attention_flops_per_token(cfg: Mapping[str, int], seq: int,
                              window: Optional[int]) -> float:
    """Forward FLOPs a token of one grouped-query attention block: W_q and
    W_o at ``num_attention_heads`` x ``head_dim``, W_k and W_v at
    ``num_key_value_heads`` x ``head_dim``, and the scores and weighted
    values of every QUERY head over the pairs its mask leaves."""
    h, H, d = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["head_dim"])
    proj = 2.0 * (2 * h * H * d + 2 * h * cfg["num_key_value_heads"] * d)
    return proj + 2.0 * H * (d + d) * attended_pairs(seq, window) / seq


def expert_flops_per_assignment(cfg: Mapping[str, int]) -> float:
    """Forward FLOPs of one row through gate, up and down."""
    return 3.0 * 2 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def held_share(cfg: Mapping[str, int]) -> float:
    """Share of a token's assignments that land on a held expert when
    loads are even: held / router width."""
    return cfg["moe_num_primary_experts"] / cfg["router_width"]


def train_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Forward + backward FLOPs per token of the configuration as this
    chip runs it: each layer's attention under its own mask, the router at
    its published width, the HELD share of the
    ``moe_num_active_primary_experts`` assignments, and the untied head
    over the held vocabulary."""
    h = cfg["hidden_size"]
    windows = layer_windows(cfg)
    forward = (sum(attention_flops_per_token(cfg, seq, w) for w in windows)
               + len(windows) * (
                   2.0 * h * cfg["router_width"]
                   + cfg["moe_num_active_primary_experts"] * held_share(cfg)
                   * expert_flops_per_assignment(cfg))
               + 2.0 * h * cfg["vocab_size"])
    return 3.0 * forward


def held_expert_flops(cfg: Mapping[str, int], assignments: float) -> float:
    """Forward + backward FLOPs of ``assignments`` rows through a held
    expert's three matrices (three passes)."""
    return 3.0 * assignments * expert_flops_per_assignment(cfg)


#: matmuls of each flash kernel, each 2 * d FLOP a (query, key) pair a head
FLASH_MATMULS = {"flash_fwd": 2,        # q k^T, p v
                 "flash_bwd_dq": 3,     # q k^T, do v^T, ds k
                 "flash_bwd_dkv": 4}    # q k^T, p^T do, do v^T, ds^T q


def flash_kernel_floor(kernel: str, cfg: Mapping[str, int], batch: int,
                       seq: int, window: Optional[int],
                       peaks: Mapping[str, float], operand_bytes: int = 2,
                       result_bytes: int = 4) -> Dict[str, float]:
    """One call of a flash kernel under one layer's mask, as
    ``harness/flops_lfm2.flash_kernel_floor`` counts the causal ones:
    ``flop`` over the pairs a QUERY head's mask leaves
    (``attended_pairs``); ``bytes`` with q, dO, o and dq at
    ``num_attention_heads`` and k, v, dk and dv at ``num_key_value_heads``
    (the repeat to 28 heads is the program's, not the model's), operands
    once in the kernels' multiply dtype, results once in the caller's, the
    row statistics one float32 a query row (forward: lse out; backward:
    lse and delta in); ``floor_s`` = the larger of flop / peak FLOP/s and
    bytes / peak bytes/s."""
    H, G, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    flop = 2.0 * d * FLASH_MATMULS[kernel] * batch * H \
        * attended_pairs(seq, window)
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


def step_flash_floor_s(kernel: str, cfg: Mapping[str, int], batch: int,
                       seq: int, peaks: Mapping[str, float]) -> float:
    """The least seconds a step's calls of ``kernel`` can take: the sum of
    ``flash_kernel_floor`` over the layers, each under its own mask."""
    return sum(flash_kernel_floor(kernel, cfg, batch, seq, w, peaks)[
        "floor_s"] for w in layer_windows(cfg))

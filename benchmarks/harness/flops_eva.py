"""Operations an EVA byte decoder needs (``evabyte-6.5b``), from shapes
alone.

As ``harness/flops.py``: only what the forward and backward passes
REQUIRE — a matmul of [m, k] x [k, n] is 2*m*k*n, backward is twice
forward; nothing recomputed or padded; gathers, norms, rotary, softmax and
the pooling of a chunk (elementwise products and sums of 16 keys) count as
zero. Attention needs the (query, key) and (query, summary) products its
MASK leaves, whatever pairs of blocks the program that implements it
walks."""

from __future__ import annotations

from typing import Dict, Mapping


def attended_products(seq: int, window: int, chunk: int) -> Dict[str, int]:
    """Products one head's mask leaves of a ``seq``-long sequence: query i
    in window n sees ``i - n * window + 1`` keys of its own window
    (``local``) and the ``n * window / chunk`` summaries of the windows
    before it (``summary``)."""
    windows = seq // window
    assert windows * window == seq and window % chunk == 0
    local = windows * (window * (window + 1) // 2)
    summary = window * (window // chunk) * (windows * (windows - 1) // 2)
    return {"local": local, "summary": summary, "all": local + summary}


def block_flops_per_token(cfg: Mapping[str, int], seq: int
                          ) -> Dict[str, float]:
    """Forward FLOPs a token of one block, by part: the four projections,
    the scores and weighted values of every head over the products its
    mask leaves, the SwiGLU's three matrices."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    d = h // H
    products = attended_products(seq, cfg["window_size"], cfg["chunk_size"])
    return {"projections": 2.0 * 4 * h * h,
            "attention": 2.0 * H * (d + d) * products["all"] / seq,
            "ffn": 2.0 * 3 * h * cfg["intermediate_size"]}


def forward_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Every block and the ``num_pred_heads`` heads over the vocabulary."""
    return (cfg["num_hidden_layers"]
            * sum(block_flops_per_token(cfg, seq).values())
            + 2.0 * cfg["hidden_size"] * cfg["num_pred_heads"]
            * cfg["vocab_size"])


def train_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Forward + backward (three passes); recomputation is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


#: matmuls of each flash kernel, each 2 * d FLOP a product a head
FLASH_MATMULS = {"flash_fwd": 2,        # q k^T, p v
                 "flash_bwd_dq": 3,     # q k^T, do v^T, ds k
                 "flash_bwd_dkv": 4}    # q k^T, p^T do, do v^T, ds^T q


def flash_kernel_floor(kernel: str, cfg: Mapping[str, int], batch: int,
                       seq: int, peaks: Mapping[str, float],
                       operand_bytes: int = 2, result_bytes: int = 4
                       ) -> Dict[str, float]:
    """One call of a flash kernel under the EVA mask, as
    ``harness/flops_swa.flash_kernel_floor`` counts a banded one: ``flop``
    over the products the MASK leaves (``attended_products``), not over
    the block pairs an implementation walks; ``bytes`` with q, dO, o and
    dq over the ``seq`` rows and k, v, dk and dv over the ``seq`` keys AND
    the ``(seq - window) / chunk`` summaries some query reads (the last
    window's are no one's past), operands once in the kernels' multiply
    dtype, results once in the caller's, the row statistics one float32 a
    query row (forward: lse out; backward: lse and delta in); ``floor_s`` =
    the larger of flop / peak FLOP/s and bytes / peak bytes/s."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    d = h // H
    products = attended_products(seq, cfg["window_size"], cfg["chunk_size"])
    flop = 2.0 * d * FLASH_MATMULS[kernel] * batch * H * products["all"]
    q_rows = batch * H * seq
    kv_rows = batch * H * (
        seq + (seq - cfg["window_size"]) // cfg["chunk_size"])
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

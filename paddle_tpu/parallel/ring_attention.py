"""Context/sequence parallelism: ring attention and Ulysses.

ABSENT in the reference (SURVEY §2.6 CP row — verified no
sequence-parallel code in that vintage); required here as a first-class
axis for long-context parity goals. Two standard formulations over the
``cp`` mesh axis:

- **Ring attention**: Q stays put, K/V blocks rotate around the ring with
  ``ppermute`` while an online-softmax accumulator merges per-block
  attention (flash-attention style log-sum-exp merge). Peak memory is one
  KV block; the ring transfer overlaps with the block matmul on ICI.
- **Ulysses**: all-to-all swaps the sharding from sequence to heads, runs
  exact local attention per head group, and swaps back. Cheaper at modest
  sequence lengths, requires heads % cp == 0.

Both are causal-capable with global position offsets. The inner block
kernel is jnp (XLA fuses well at these sizes); a Pallas flash kernel can
replace `_block_attn` without touching the ring logic.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.enforce import enforce_eq
from ..ops import collectives as coll

__all__ = ["ring_attention", "ring_flash_attention", "ulysses_attention", "local_attention"]


def _block_scores(q, k, scale):
    # q: [B, Lq, H, D], k: [B, Lk, H, D] → [B, H, Lq, Lk]
    return jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale


def local_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    q_offset: int | jax.Array = 0, k_offset: int | jax.Array = 0,
    window: int | None = None,
) -> jax.Array:
    """Plain softmax attention on local blocks ([B, L, H, D] layout);
    under ``window`` (causal) query i sees the keys i - window < j <= i."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    scores = _block_scores(q, k, scale)
    if causal:
        qi = jnp.arange(q.shape[1])[:, None] + q_offset
        ki = jnp.arange(k.shape[1])[None, :] + k_offset
        seen = ki <= qi
        if window is not None:
            seen = seen & (ki > qi - window)
        scores = jnp.where(seen, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = "cp",
    causal: bool = False,
) -> jax.Array:
    """Ring attention whose per-hop block attention is the Pallas flash
    kernel (ops/flash_attention.py): each hop computes the local
    (out, lse) for the KV block currently held, and the carry merges
    partials with lse weights (log-add-exp combine). Differentiable —
    flash's VJP handles dlse. Use on TPU; einsum `ring_attention` is the
    interpret-friendly fallback."""
    from ..ops.flash_attention import flash_attention_with_lse

    P = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    B, L, H, D = q.shape
    q_off = rank * L
    NEG = -1e30

    def merge(out, lse, k_cur, v_cur, i):
        src = (rank - i) % P
        o_i, lse_i = flash_attention_with_lse(
            q, k_cur, v_cur, causal=causal, q_offset=q_off, k_offset=src * L)
        lse_new = jnp.logaddexp(lse, lse_i)
        w_prev = jnp.exp(lse - lse_new)
        w_cur = jnp.exp(lse_i - lse_new)
        out_new = out * w_prev[..., None] + o_i * w_cur[..., None]
        return out_new, lse_new

    def step(carry, i):
        out, lse, k_cur, v_cur = carry
        out, lse = merge(out, lse, k_cur, v_cur, i)
        return (out, lse, coll.shift(k_cur, axis, 1),
                coll.shift(v_cur, axis, 1)), None

    out0 = jnp.zeros_like(q)
    lse0 = jnp.sum(q.astype(jnp.float32), axis=-1) * 0.0 + NEG  # [B, L, H], q's vma
    (out, lse, k_last, v_last), _ = lax.scan(
        step, (out0, lse0, k, v), jnp.arange(P - 1))
    out, _ = merge(out, lse, k_last, v_last, P - 1)
    return out


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = "cp",
    causal: bool = False,
) -> jax.Array:
    """Blockwise ring attention inside shard_map.

    q/k/v: [B, L_local, H, D] — the local sequence shard. Rotates KV
    around the cp ring, merging blocks with a numerically stable online
    softmax. Fully masked blocks (causal, future ranks) contribute zero.
    """
    P = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    B, L, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, q.dtype))
    q_off = rank * L

    neg_big = jnp.asarray(-1e30, jnp.float32)

    def merge_block(out, m, denom, k_cur, v_cur, i):
        """Online-softmax merge of the KV block received after i hops."""
        src = (rank - i) % P  # whose KV block we now hold
        scores = _block_scores(q, k_cur, scale).astype(jnp.float32)  # [B,H,Lq,Lk]
        if causal:
            qi = jnp.arange(L)[:, None] + q_off
            ki = jnp.arange(L)[None, :] + src * L
            scores = jnp.where(ki <= qi, scores, neg_big)
        m_blk = jnp.max(scores, axis=-1)  # [B,H,Lq]
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows: exp(neg_big - neg_big) would be 1
        alive = m_new > neg_big * 0.5
        p = jnp.exp(scores - m_new[..., None])
        p = jnp.where(alive[..., None], p, 0.0)
        corr = jnp.where(alive, jnp.exp(m - m_new), 0.0)
        denom_new = denom * corr + jnp.sum(p, axis=-1)
        pv_ = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v_cur)
        out_new = out * corr.transpose(0, 2, 1)[..., None] + pv_
        return out_new, m_new, denom_new

    def step(carry, i):
        out, m, denom, k_cur, v_cur = carry
        out, m, denom = merge_block(out, m, denom, k_cur, v_cur, i)
        k_next = coll.shift(k_cur, axis, 1)
        v_next = coll.shift(v_cur, axis, 1)
        return (out, m, denom, k_next, v_next), None

    # constants entering the scan carry must carry the same
    # varying-manual-axes type as the rotated KV blocks they mix with —
    # derive them from q so they inherit its full vma set (q may vary
    # over dp/other axes too when the batch is sharded)
    out0 = jnp.zeros_like(q)  # inherits 'varying' from q
    zeros_bhl = jnp.sum(q, axis=-1).transpose(0, 2, 1).astype(jnp.float32) * 0.0
    m0 = zeros_bhl + neg_big
    d0 = zeros_bhl
    # P-1 rotate-and-merge steps in the scan, then merge the final block
    # outside it — the last rotation's result would be discarded, and a
    # full-KV ppermute per layer is real ICI bandwidth
    (out, m, denom, k_last, v_last), _ = lax.scan(
        step, (out0, m0, d0, k, v), jnp.arange(P - 1)
    )
    out, m, denom = merge_block(out, m, denom, k_last, v_last, P - 1)
    denom = jnp.maximum(denom, 1e-30)
    return out / denom.transpose(0, 2, 1)[..., None].astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = "cp",
    causal: bool = False,
) -> jax.Array:
    """Ulysses (all-to-all head/sequence swap) inside shard_map.

    q/k/v: [B, L_local, H, D]; requires H % cp_size == 0. After the
    exchange each rank holds the FULL sequence for H/cp heads, so the
    local attention is exact (no online merge) and causal masking needs
    no offsets.
    """
    Pn = lax.axis_size(axis)
    B, L, H, D = q.shape
    enforce_eq(H % Pn, 0, "heads must divide cp size for ulysses")

    def seq_to_heads(x):  # [B, L, H, D] → [B, L*P, H/P, D]
        return coll.all_to_all(x, axis, split_axis_=2, concat_axis=1)

    def heads_to_seq(x):  # inverse
        return coll.all_to_all(x, axis, split_axis_=1, concat_axis=2)

    qf, kf, vf = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = local_attention(qf, kf, vf, causal=causal)
    return heads_to_seq(out)

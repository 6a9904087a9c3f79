"""EVA attention (Zheng et al., ICLR 2023, arXiv:2302.04542) as EvaByte
runs it: a query attends, under ONE softmax, to the causal keys of its own
aligned ``window`` and to a learned summary of every ``chunk`` keys of the
windows before it.

    w_j  = softmax_{j in chunk m}(scale · k_j·φ)                  (float32)
    k̃_m = Σ_j w_j k_j + μ        ṽ_m = Σ_j w_j v_j
    o_i  = softmax over {k_j : j in i's window, j <= i} ∪ {k̃_m : chunk m in
           an earlier window} of scale · q_i·(that key), times the values

``chunk_summaries`` is plain ``jnp`` under ``pt.eva.prep``, differentiated
by JAX: it reads k and v once and writes 1 / ``chunk`` of them.
``eva_attention`` hands the flash kernels ``[summaries ‖ keys]`` under the
mask that says so (``flash_attention.Mask``): one call, one running
softmax, one ``lse``; the backward's ``dk``, ``dv`` split by the
concatenation's own transpose, and the summaries' part flows on through the
pooling to k, v, φ and μ. Only the summaries some query reads are made: the
last window's chunks are nobody's earlier window.
``eva_attention_einsum`` is the same function as explicit scores under an
explicit mask, a block of queries at a time: the off-TPU stand-in for the
kernels and the float32 side of the benchmark's check.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.enforce import enforce
from .flash_attention import Keys, Mask, flash_attention

__all__ = ["chunk_summaries", "eva_mask", "eva_attention",
           "eva_attention_einsum"]

#: queries a block of the einsum form
_QUERY_BLOCK = 1024


def chunk_summaries(k: jax.Array, v: jax.Array, phi: jax.Array,
                    mu: jax.Array, chunk: int, scale: float
                    ) -> Tuple[jax.Array, jax.Array]:
    """``(k̃, ṽ)`` [B, L / chunk, H, d] of k, v [B, L, H, d]: each chunk's
    keys and values pooled by the softmax of ``scale · k·φ`` over the
    chunk, ``μ`` added to the pooled key. φ, μ [H, d]; float32."""
    B, L, H, d = k.shape
    enforce(L % chunk == 0, f"{L} keys are no whole chunks of {chunk}")
    with jax.named_scope("pt.eva.prep"):
        kc = k.astype(jnp.float32).reshape(B, L // chunk, chunk, H, d)
        vc = v.astype(jnp.float32).reshape(B, L // chunk, chunk, H, d)
        w = jax.nn.softmax(jnp.sum(kc * phi, axis=-1) * scale, axis=2)
        w = w[..., None]                               # [B, M, chunk, H, 1]
        return jnp.sum(w * kc, axis=2) + mu, jnp.sum(w * vc, axis=2)


def eva_mask(L: int, window: int, chunk: int) -> Mask:
    """The stated mask of ``[summaries ‖ keys]`` over ``L`` positions: the
    summaries of the chunks of all windows but the last, each seen by the
    rows of LATER windows; then the keys, each seen by the rows of its own
    window from itself on."""
    enforce(L % window == 0 and window % chunk == 0,
            f"{L} positions in aligned windows of {window}, chunks of "
            f"{chunk}: whole windows of whole chunks")
    if L == window:
        return Mask((Keys(count=L),), aligned=window)
    return Mask((Keys(count=(L - window) // chunk, stride=chunk,
                      earlier=True), Keys(count=L)), aligned=window)


def _with_summaries(k, v, phi, mu, window, chunk, scale):
    """``[summaries ‖ keys]`` and the values alike, in k's dtype."""
    seen = k.shape[1] - window       # the keys some later window reads pooled
    if not seen:
        return k, v
    ks, vs = chunk_summaries(k[:, :seen], v[:, :seen], phi, mu, chunk, scale)
    return (jnp.concatenate([ks.astype(k.dtype), k], axis=1),
            jnp.concatenate([vs.astype(v.dtype), v], axis=1))


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array,
                  mu: jax.Array, window: int, chunk: int,
                  precision: str = "default",
                  interpret: Optional[bool] = None) -> jax.Array:
    """[B, L, H, d] → [B, L, H, d] through the flash kernels, in blocks of
    512 (of a window, where that is less: a q block lies in one window).
    ``precision`` is ``flash_attention``'s: ``"highest"`` has the kernels
    multiply float32 operands, else bf16."""
    L, d = q.shape[1], q.shape[-1]
    keys, values = _with_summaries(k, v, phi, mu, window, chunk, d ** -0.5)
    block = min(512, window)
    return flash_attention(q, keys, values, mask=eva_mask(L, window, chunk),
                           block_q=block, block_k=block, interpret=interpret,
                           precision=precision)


def eva_attention_einsum(q: jax.Array, k: jax.Array, v: jax.Array,
                         phi: jax.Array, mu: jax.Array, window: int,
                         chunk: int) -> jax.Array:
    """The same function as explicit scores: ``_QUERY_BLOCK`` queries of
    every head against ``[summaries ‖ keys]`` at a time, rebuilt in the
    backward pass (32 heads' [1024, 8576] scores are 1.1 GB; [8192, 8576]
    would be 9)."""
    B, L, H, d = q.shape
    scale = d ** -0.5
    eva_mask(L, window, chunk)                  # the same sizes are refused
    keys, values = _with_summaries(k, v, phi, mu, window, chunk, scale)
    S = keys.shape[1] - L
    # a summary's last key; a key's own position
    pos = jnp.concatenate([jnp.arange(S) * chunk + chunk - 1, jnp.arange(L)])
    pooled = jnp.arange(S + L) < S
    bq = _QUERY_BLOCK if L % _QUERY_BLOCK == 0 else L

    @jax.checkpoint
    def block(args):
        qb, first = args
        rows = first + jnp.arange(bq)[:, None]
        start = rows - rows % window
        seen = jnp.where(pooled, pos < start, (pos >= start) & (pos <= rows))
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, keys) * scale
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, values)

    blocks = jnp.moveaxis(q.reshape(B, L // bq, bq, H, d), 1, 0)
    out = jax.lax.map(block, (blocks, jnp.arange(L // bq) * bq))
    return jnp.moveaxis(out, 0, 1).reshape(B, L, H, d)

"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): a token's state between sublayers is
``n`` residual streams ``X`` [n, C], and each sublayer ``F`` is wrapped by
three mappings made from the streams themselves.

    x̄      = vec(X) / sqrt(mean(vec(X)²) + rms_eps)               (float32)
    H̃_pre  = α_pre  · (x̄ Φ_pre)  + b_pre    [n]      H_pre  = σ(H̃_pre)
    H̃_post = α_post · (x̄ Φ_post) + b_post   [n]      H_post = 2 σ(H̃_post)
    H̃_res  = α_res  · mat(x̄ Φ_res) + b_res  [n, n]   H_res  = SK(clip(H̃_res))
    SK(A):   M = exp(A); ``iters`` times: every column over (its sum + eps),
             then every row over (its sum + eps)    -> doubly stochastic
    u = H_pre X     y = F(u)     X'_i = Σ_j H_res[i, j] X_j + H_post[i] y

``Φ = [Φ_pre | Φ_post | Φ_res]`` is ONE [nC, 2n + n²] matrix, ``b`` one
[2n + n²] vector, ``α`` three scalars, all learned, one set a sublayer.
Plain ``jnp``, differentiated by JAX through every Sinkhorn step (a
``lax.scan`` of ``iters`` steps on [tokens, n, n]). The
mappings are float32 whatever ``amp`` says: the projection is ONE
[tokens, nC] x [nC, 2n + n²] matmul at precision ``highest`` with the
norm's factor applied to its 2n + n² results (x̄ itself is never made), so
nothing of the size [tokens, nC, n²] exists. ``hc_collect`` and
``hc_scatter`` read the streams in their own dtype, add in float32 and —
the n x n contraction written out stream by stream, elementwise, for XLA to
fuse into one pass — leave ``u`` float32 and ``X'`` in the streams' dtype.
The callers open the scopes (``pt.hc.map`` / ``pt.hc.collect`` /
``pt.hc.scatter``: ``models/transformer.HyperConnected``).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.enforce import enforce

__all__ = ["sinkhorn", "hc_mappings", "hc_collect", "hc_scatter",
           "hc_res_err"]


def sinkhorn(a: jax.Array, iters: int, eps: float) -> jax.Array:
    """``SK(a)`` of [..., n, n] float32 (row i, column j): ``exp``, then
    ``iters`` times columns (each over its sum + ``eps``) and rows
    (likewise). Rows end within ``eps`` of 1, columns as near as the
    iteration has come. The steps are a ``lax.scan``, which the backward
    pass takes (it keeps each step's matrix, ``iters`` x [..., n, n]): one
    step's program, not ``iters`` copies of it — unrolled, the Sinkhorn
    steps were 2,200 of the cell's 4,762 fusions and its executable 340 MiB
    (CPU compile for a described v5e, PR 51), and XLA's simplifier turned
    the chain of divisions into a division by the product of all earlier
    divisors, step after step."""
    def step(m, _):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m, None

    return lax.scan(step, jnp.exp(a), None, length=iters)[0]


def hc_mappings(x: jax.Array, phi: jax.Array, b: jax.Array, alpha: jax.Array,
                iters: int, eps: float, clamp: Tuple[float, float],
                rms_eps: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` of the
    streams ``x`` [..., n, C], float32. ``clamp`` bounds ``H̃_res`` before
    the ``exp``."""
    n, c = x.shape[-2:]
    enforce(n >= 2, f"hyper-connections over {n} stream: at least two (one "
            "stream is the plain residual block, which has no mapping)")
    enforce(phi.shape == (n * c, 2 * n + n * n) and b.shape == (phi.shape[1],)
            and alpha.shape == (3,),
            f"mappings of {n} streams of {c}: phi {phi.shape}, b {b.shape}, "
            f"alpha {alpha.shape}")
    flat = x.reshape(*x.shape[:-2], n * c).astype(jnp.float32)
    inv_rms = lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                        + rms_eps)
    z = jnp.dot(flat, phi.astype(jnp.float32),
                precision=lax.Precision.HIGHEST) * inv_rms
    # alpha_pre on the first n results, alpha_post on the next n, alpha_res
    # on the n x n that follow
    gate = alpha.astype(jnp.float32)[np.repeat(np.arange(3), (n, n, n * n))]
    z = z * gate + b.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(z[..., :n])
    h_post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    res = z[..., 2 * n:].reshape(*z.shape[:-1], n, n)
    return h_pre, h_post, sinkhorn(jnp.clip(res, clamp[0], clamp[1]),
                                   iters, eps)


def hc_collect(x: jax.Array, h_pre: jax.Array) -> jax.Array:
    """``u = H_pre X`` [..., C] float32: the sublayer's input."""
    n = x.shape[-2]
    return sum(h_pre[..., j, None] * x[..., j, :].astype(jnp.float32)
               for j in range(n))


def hc_scatter(x: jax.Array, y: jax.Array, h_post: jax.Array,
               h_res: jax.Array) -> jax.Array:
    """``X' = H_res X + H_postᵀ y`` [..., n, C] in ``x``'s dtype, the sums
    float32."""
    n = x.shape[-2]
    mixed = sum(h_res[..., :, j, None]
                * x[..., None, j, :].astype(jnp.float32) for j in range(n))
    out = mixed + h_post[..., :, None] * y[..., None, :].astype(jnp.float32)
    return out.astype(x.dtype)


def hc_res_err(h_res: jax.Array) -> jax.Array:
    """The largest ``|row sum - 1|`` and ``|column sum - 1|`` of ``H_res``
    over all its tokens: how far the constraint is from holding."""
    rows = jnp.abs(jnp.sum(h_res, axis=-1) - 1.0)
    cols = jnp.abs(jnp.sum(h_res, axis=-2) - 1.0)
    return jnp.maximum(jnp.max(rows), jnp.max(cols))

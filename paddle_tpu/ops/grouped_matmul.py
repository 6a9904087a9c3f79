"""Grouped matmul — the expert banks' kernel: every row of ``x`` times the
matrix of its own expert, rows grouped by expert with group sizes known at
run time (``parallel/moe.py``'s dropless and held layers run it between
their dispatch and their combine).

The Pallas kernels that ship with jax (``megablox``: ``gmm`` forward and for
dx, ``tgmm`` for the bank's gradient) under one custom VJP, tiles chosen on
the chip and cut to the operands. Mosaic's payload carries this file's
source positions, so a line added above ``_gmm_call`` gives every step that
runs it another compile-cache key (one cold compile, no other effect).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
# from the module by its path: the package's own ``gmm`` attribute is its
# custom-VJP wrapper, not the module
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm_kernel
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _tgmm_kernel

__all__ = ["grouped_matmul", "expert_ffn"]


#: (rows, contraction, columns) tile of the grouped-matmul kernel by operand
#: width in bytes. bf16: the fastest of nine tried on the v5e at
#: [65536, 2048] x [64, 2048, 1024] (7.2 ms for the three passes; the
#: kernel's default 128^3: 92 ms; 512 x 1024 x 1024 and larger: over the
#: kernel's VMEM). float32: half the columns, for the same VMEM.
#: ``tools/grouped_matmul_bench.py``, PERF.md section 6. A tile is cut to
#: the dimension where that is smaller.
_GMM_TILE = {2: (256, 1024, 1024), 4: (256, 512, 512)}


def _fit_tile(tile: int, dim: int) -> int:
    """``tile`` cut to ``dim``; where ``dim`` is larger and no multiple of
    it (an expert width of 768 under 512), the largest multiple of 128
    lanes under ``tile`` that divides ``dim`` (384), so that no tile of
    the kernel is a partial one."""
    if dim <= tile or dim % tile == 0:
        return min(tile, dim)
    fits = [t for t in range(tile - tile % 128, 0, -128) if dim % t == 0]
    return fits[0] if fits else tile


def _gmm_call(kernel, lhs, rhs, group_sizes, out_dtype, k, n, **kw):
    """``kernel`` on a [., k] x [k, n] product, tiles cut to k and n."""
    tm, tk, tn = _GMM_TILE[lhs.dtype.itemsize]
    return kernel(lhs, rhs, group_sizes, out_dtype,
                  (tm, _fit_tile(tk, k), _fit_tile(tn, n)),
                  interpret=jax.default_backend() != "tpu", **kw)


@jax.custom_vjp
def _gmm(x: jax.Array, bank: jax.Array, group_sizes: jax.Array) -> jax.Array:
    _, k, n = bank.shape
    return _gmm_call(_gmm_kernel, x, bank, group_sizes, jnp.float32, k, n)


def _gmm_bwd(res, g):
    # both backward products with the cotangent in the operands' dtype:
    # dx = g @ bank^T row by row's expert, dbank[e] = x_e^T @ g_e
    x, bank, group_sizes = res
    E, k, n = bank.shape
    g = g.astype(x.dtype)
    dx = _gmm_call(_gmm_kernel, g, bank, group_sizes, x.dtype, n, k,
                   transpose_rhs=True)
    dbank = _gmm_call(_tgmm_kernel, x.swapaxes(0, 1), g, group_sizes,
                      bank.dtype, k, n, num_actual_groups=E)
    return dx, dbank, None


_gmm.defvjp(lambda x, bank, gs: (_gmm(x, bank, gs), (x, bank, gs)), _gmm_bwd)


def grouped_matmul(x: jax.Array, bank: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``x`` [M, d] with rows grouped by expert (``group_sizes`` [E], known
    at run time; rows past their sum are no expert's) times ``bank``
    [E, d, f]: row i meets its own expert's matrix; float32 out. The
    Pallas grouped-matmul kernel that ships with jax
    (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and for
    dx, ``tgmm`` for the bank's gradient), interpreted off TPU — chosen on
    the chip against ``jax.lax.ragged_dot``: 7.7 ms against 14.8 for the
    three passes at [65536, 2048] x [64, 2048, 1024], and XLA:TPU renames
    its own ragged-dot kernels ``ragged-dot-none``, outside every ``pt.*``
    scope (``tools/grouped_matmul_bench.py``; PERF.md section 6).
    Under ``amp.auto_cast`` the operands go in the amp dtype with float32
    accumulation, as ``nn.functional.linear``'s do."""
    from .. import amp

    if amp.amp_enabled() and bank.dtype == jnp.float32:
        dt = amp.amp_dtype()
        x, bank = x.astype(dt), bank.astype(dt)
    m = x.shape[0]
    pad = -m % _GMM_TILE[x.dtype.itemsize][0]      # whole row tiles
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return _gmm(x, bank, group_sizes)[:m]


def expert_ffn(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
               w_down: jax.Array, group_sizes: jax.Array,
               activation: Callable = jax.nn.silu) -> jax.Array:
    """Gated feed-forward of every row through its own expert:
    ``down(activation(gate(x)) * up(x))`` (SiLU unless told otherwise),
    three grouped matmuls, no bias."""
    gate = grouped_matmul(x, w_gate, group_sizes)
    up = grouped_matmul(x, w_up, group_sizes)
    return grouped_matmul(activation(gate) * up, w_down, group_sizes)

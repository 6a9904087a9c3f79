"""Pallas flash attention for TPU.

The hot op the reference leaves to cuDNN/hand-CUDA becomes a Pallas
kernel pair (fwd + bwd) built for the MXU: blockwise QK^T with an online
softmax held in VMEM scratch, O accumulated in fp32, causal blocks
skipped whole. Returns the per-row log-sum-exp so the cp ring
(parallel/ring_attention.py) can merge per-device partial attentions
without renormalizing through HBM.

Layout: [B, L, H, D] (framework-wide attention layout); internally
reshaped to [B*H, L, D] and padded to MXU tiles (D→128 multiples,
L→block multiples). v may have a width of its own (latent attention:
q.k at 192, P.v at 128): q and k are padded to their lane multiple (256),
v, dO, the output and dv to v's (128) — never v to q's, which would
double P.v and the result's bytes. For equal widths the kernels are the
ones they were. On the chip the minor dimension of a kernel operand
occupies whole 128-lane tiles in HBM whatever its logical size (an
``[…, 64]`` operand gets ``T(8,128)`` tiles too), so the pad costs no
bytes beyond what the layout already does; what halves them is the dtype.
The kernels multiply in ``mxu`` = bf16 (float32 under
``precision="highest"``), so q, k, v and dO are HANDED to them in ``mxu``:
the convert is done inside the custom VJP — primals, cotangents and every
result keep the caller's dtype — fuses into the producer that writes the
padded operand, and the saved residuals are the narrow ones. This is
exact: the rounding only moves from the kernel's first line to its
producer's last (``pt.flash.operands`` records the width, one host span a
trace). A producer that does that rounding itself (``mxu_rounded`` on the
QKV matmul's output) keeps the moved bytes under its own scope's name.
Row statistics travel lane-padded, ``f32[B*H, L, 128]``: the
forward writes lse broadcast over the lanes, the backward reads ONE such
array with lse in lane 0 and Δ in lane 1. Inside the forward they STAY
lane-wide from pair to pair (PERF.md §6, PR 45): the running max is held
replicated over its 128 lanes and read and written whole, the running sum
as 128 lane-partial sums (VPU adds of the tile's lane groups) that are
reduced across lanes once, on a run's last step — a ``[rows, 1]`` statistic
sliced out of and broadcast back into the lanes is a lane shuffle a vreg,
and those, not the matmuls, were what a pair waited for.
``q_offset``/``k_offset`` shift the causal mask for sequence-sharded (cp)
blocks; they may be traced values (axis_index).

A causal call whose offsets are Python ints walks a LIST of (q block,
k block) pairs, built with numpy at trace time: the pairs the mask leaves
something of, ``grid = (BH, pairs)``, the block numbers read from
scalar-prefetch tables. A grid step of the rectangle that the mask empties
still fetches its blocks and costs 0.6–1.0 µs (PERF.md §6, PR 41); at
L = 4096 in 512-blocks that is 28 of 64 a head. The kernel bodies are the
rectangle's own — the same mask, the same ``pl.when`` — so a walked pair
does what its grid step did. Bidirectional calls, traced offsets and a
call in which some block would have no pair keep the rectangle.

``window`` (a Python int; causal calls with Python-int offsets only) is a
second bound on that list and a second term of that mask: query ``i``
sees the keys ``i - window < j <= i``. The pairs wholly below the band are
left out as those wholly above the diagonal are; the body masks both edges
wherever it runs, which changes nothing inside the band. A window that
reaches every key of the call is no window: ``window=None`` and such a
call trace to the same program, the one a causal call always had.

Backward: standard flash backward — recompute P = exp(S - lse) blockwise;
dV = P^T dO, dS = P ∘ (dO V^T - Δ), dQ = dS K, dK = dS^T Q with
Δ = rowsum(dO ∘ O) computed outside (one fused elementwise pass).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.enforce import enforce
from ..core.profiler import RecordEvent

__all__ = ["flash_attention", "flash_attention_with_lse", "mxu_rounded"]

NEG = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _out_struct(shape, dtype, *inputs):
    """ShapeDtypeStruct carrying the union of the inputs' varying-manual-
    axes type — required for pallas_call under shard_map (check_vma)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# The block pairs a causal call walks
# ---------------------------------------------------------------------------


def _static_offsets(q_offset, k_offset):
    """``(q_off, k_off)`` where both are known at trace time, else None.
    Read where the caller's values still are what they were: inside the
    ``custom_vjp``s a Python 0 is a tracer like the cp ring's
    ``axis_index``."""
    if all(isinstance(o, (int, np.integer)) for o in (q_offset, k_offset)):
        return int(q_offset), int(k_offset)
    return None


def _causal_pairs(nq, nk, bq, bk, causal, offsets, window=None):
    """bool ``[nq, nk]``: the block pairs a causal call walks — those with
    ``q_off + (i+1)·bq − 1 ≥ k_off + j·bk``, the kernels' own ``pl.when``,
    and under a ``window`` those that also reach into the band: the
    block's last key ``k_off + (j+1)·bk − 1 > q_off + i·bq − window``.
    None where the call keeps the rectangle: it is bidirectional,
    ``offsets`` is None (they are data), or some block would have no pair
    to name it — a q block before the first key, a k block past the last
    query's reach — whose zeros only the rectangle's first and last steps
    write."""
    if not causal or offsets is None:
        return None
    q_off, k_off = offsets
    last_row = q_off + (np.arange(nq)[:, None] + 1) * bq - 1
    first_col = k_off + np.arange(nk)[None, :] * bk
    keep = last_row >= first_col
    if window is not None:
        keep &= first_col + bk - 1 > last_row - (bq - 1) - window
    if not (keep.any(axis=1).all() and keep.any(axis=0).all()):
        return None
    return keep


def _pair_tables(keep, k_major=False):
    """The scalar-prefetch tables of a pair list, int32 ``[pairs]`` each:
    the q block, the k block, and ``ends`` — bit 0 on the first pair of a
    run, bit 1 on its last. A run is the pairs of one q block, ascending
    in k (``flash_fwd``, ``flash_bwd_dq``: q blocks ascending), or with
    ``k_major`` of one k block, ascending in q (``flash_bwd_dkv``)."""
    if k_major:
        run, i = np.nonzero(keep.T)
        j = run
    else:
        run, j = np.nonzero(keep)
        i = run
    edge = run[1:] != run[:-1]
    ends = np.r_[True, edge] + 2 * np.r_[edge, True]
    return tuple(jnp.asarray(t, jnp.int32) for t in (i, j, ends))


def _grid(BH, nq, nk, keep, k_major=False):
    """``(grid, tables, q-side index map, k-side index map)`` of a call:
    the pair list ``keep`` holds, else the rectangle — ``(BH, nq, nk)``,
    or ``(BH, nk, nq)`` with ``k_major`` (the inner axis is the run)."""
    if keep is not None:
        tables = _pair_tables(keep, k_major)
        return ((BH, len(tables[0])), tables,
                lambda b, p, offs, qi, kj, ends: (b, qi[p], 0),
                lambda b, p, offs, qi, kj, ends: (b, kj[p], 0))
    if k_major:
        return ((BH, nk, nq), (),
                lambda b, j, i, offs: (b, i, 0),
                lambda b, j, i, offs: (b, j, 0))
    return ((BH, nq, nk), (),
            lambda b, i, j, offs: (b, i, 0),
            lambda b, i, j, offs: (b, j, 0))


def _grid_step(refs, listed, k_major=False):
    """Inside a kernel: ``(q block, k block, first step of its run, last
    step)`` of this grid step, and the kernel's own refs — from the pair
    list's tables, the first three of ``refs``, where the call is
    ``listed``, else off the rectangle's grid."""
    if listed:
        (qi_ref, kj_ref, ends_ref), refs = refs[:3], refs[3:]
        p = pl.program_id(1)
        ends = ends_ref[p]
        return (qi_ref[p], kj_ref[p], (ends & 1) != 0, (ends & 2) != 0), refs
    outer, inner = pl.program_id(1), pl.program_id(2)
    i, j = (inner, outer) if k_major else (outer, inner)
    return (i, j, inner == 0, inner == pl.num_programs(2) - 1), refs


def _reached(row0, col0, bq, bk, window):
    """Inside a kernel: whether the causal mask, and the window's where
    there is one, leave anything of the block at ``(row0, col0)``."""
    some = row0 + bq - 1 >= col0
    if window is not None:
        some = some & (col0 + bk - 1 > row0 - window)
    return some


def _semantics(grid):
    return pltpu.CompilerParams(dimension_semantics=(
        ("parallel",) * (len(grid) - 1) + ("arbitrary",)))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _lane_sums(p):
    """``[rows, 128]`` whose sum across lanes is ``p``'s row sum: ``p``'s
    128-lane groups added to each other (VPU adds, no cross-lane
    reduction); where ``p``'s width is no lane multiple (blocks under 128
    keys: the CPU tests'), the row sum in lane 0."""
    rows, n = p.shape
    if n % 128:
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, 128), 1)
        return jnp.where(lane == 0, jnp.sum(p, axis=-1, keepdims=True), 0.0)
    return functools.reduce(
        jnp.add, (p[:, t:t + 128] for t in range(0, n, 128)))


def _across(x, n):
    """A lane-replicated ``[rows, 128]`` statistic beside ``n`` columns:
    its vregs again for every 128-lane group (no shuffle); one column to
    broadcast where ``n`` is no lane multiple."""
    if n % 128:
        return x[:, :1]
    return x if n == 128 else jnp.tile(x, (1, n // 128))


def _fwd_kernel(offs_ref, *refs, scale, causal, bq, bk, mxu, listed,
                window=None):
    (i, j, first, last), refs = _grid_step(refs, listed)
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
    # m_scr: the running max, replicated over its lanes; l_scr: the running
    # sum as lane-partial sums — both [bq, 128], read and written whole

    @pl.when(first)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    q_off, k_off, k_len = offs_ref[0], offs_ref[1], offs_ref[3]
    row0 = q_off + i * bq
    col0 = k_off + j * bk

    def body():
        # MXU operands in `mxu` dtype (bf16 default: single-pass MXU with
        # fp32 accumulation; fp32 operands = multi-pass, ~3x the cycles)
        q = q_ref[0].astype(mxu)          # [bq, D]
        k = k_ref[0].astype(mxu)          # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < (k_off + k_len)
        if causal:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (cols <= rows)
            if window is not None:
                mask = mask & (cols > rows - window)
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[:]                          # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a masked key is exp(NEG - m) = 0 beside any key the row has met;
        # a row that has met none yet (m_new = NEG: a band edge inside the
        # block) takes its exponent from 0, which gives the same zeros
        m_exp = jnp.where(m_new > NEG / 2, m_new, 0.0)
        p = jnp.exp(s - _across(m_exp, bk))
        corr = jnp.exp(m_prev - m_new)             # m_prev=NEG → 0
        l_scr[:] = l_scr[:] * corr + _lane_sums(p)
        acc[:] = acc[:] * _across(corr, acc.shape[-1]) + jax.lax.dot_general(
            p.astype(mxu), v_ref[0].astype(mxu),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    if causal:
        # causal block skip: block fully in the future → nothing to do
        pl.when(_reached(row0, col0, bq, bk, window))(body)
    else:
        body()

    @pl.when(last)
    def _():
        # the run's one reduction of the sum across lanes, replicated over
        # them again: lse leaves as the statistics were kept
        l = jnp.broadcast_to(jnp.sum(l_scr[:], axis=-1, keepdims=True),
                             l_scr.shape)
        safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc[:] / _across(safe, acc.shape[-1])).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l > 0, m_scr[:] + jnp.log(safe), NEG)


def _fwd(q, k, v, scale, causal, q_offset, k_offset, bq, bk, interpret, mxu,
         dtype, keep, window=None):
    BH, Lq, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]      # q.k at D, P.v and the result at Dv
    nq, nk = Lq // bq, Lk // bk
    offs = jnp.asarray(
        jnp.stack([jnp.asarray(q_offset, jnp.int32),
                   jnp.asarray(k_offset, jnp.int32),
                   jnp.asarray(Lq, jnp.int32),
                   jnp.asarray(k.shape[1], jnp.int32)]), jnp.int32)

    grid, tables, at_q, at_k = _grid(BH, nq, nk, keep)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, mxu=mxu, listed=bool(tables),
                               window=window)
    with jax.named_scope("pt.flash_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1 + len(tables),
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, bq, D), at_q),
                    pl.BlockSpec((1, bk, D), at_k),
                    pl.BlockSpec((1, bk, Dv), at_k),
                ],
                out_specs=[
                    pl.BlockSpec((1, bq, Dv), at_q),
                    pl.BlockSpec((1, bq, 128), at_q),
                ],
                scratch_shapes=[
                    pltpu.VMEM((bq, Dv), jnp.float32),
                    pltpu.VMEM((bq, 128), jnp.float32),
                    pltpu.VMEM((bq, 128), jnp.float32),
                ],
            ),
            out_shape=[
                _out_struct((BH, Lq, Dv), dtype, q, k, v, offs),
                _out_struct((BH, Lq, 128), jnp.float32, q, k, v, offs),
            ],
            compiler_params=_semantics(grid),
            name="flash_fwd",
            interpret=interpret,
        )(offs, *tables, q, k, v)
    return out, lse[:, :, 0]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(offs_ref, *refs, scale, causal, bq, bk, mxu, listed,
                   window=None):
    (i, j, first, last), refs = _grid_step(refs, listed)
    q_ref, k_ref, v_ref, do_ref, stats_ref, dq_ref, dq_acc = refs

    @pl.when(first)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_off, k_off, k_len = offs_ref[0], offs_ref[1], offs_ref[3]
    row0 = q_off + i * bq
    col0 = k_off + j * bk

    def body():
        q = q_ref[0].astype(mxu)
        k = k_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < (k_off + k_len)
        if causal:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (cols <= rows)
            if window is not None:
                mask = mask & (cols > rows - window)
        stats = stats_ref[0]
        lse, delta = stats[:, :1], stats[:, 1:2]
        p = jnp.where(mask & (lse > NEG / 2), jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do_ref[0].astype(mxu),
                                 v_ref[0].astype(mxu),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(ds.astype(mxu), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(_reached(row0, col0, bq, bk, window))(body)
    else:
        body()

    @pl.when(last)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, *refs, scale, causal, bq, bk, mxu, listed,
                    window=None):
    # a run is one k block: the q blocks are the inner loop
    (i, j, first, last), refs = _grid_step(refs, listed, k_major=True)
    (q_ref, k_ref, v_ref, do_ref, stats_ref, dk_ref, dv_ref,
     dk_acc, dv_acc) = refs

    @pl.when(first)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_off, k_off, k_len = offs_ref[0], offs_ref[1], offs_ref[3]
    row0 = q_off + i * bq
    col0 = k_off + j * bk

    def body():
        q = q_ref[0].astype(mxu)
        k = k_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols < (k_off + k_len)
        if causal:
            rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask = mask & (cols <= rows)
            if window is not None:
                mask = mask & (cols > rows - window)
        stats = stats_ref[0]
        lse, delta = stats[:, :1], stats[:, 1:2]
        p = jnp.where(mask & (lse > NEG / 2), jnp.exp(s - lse), 0.0)
        do = do_ref[0].astype(mxu)
        dv_acc[:] += jax.lax.dot_general(p.astype(mxu), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(mxu),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_acc[:] += jax.lax.dot_general(ds.astype(mxu), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32) * scale

    if causal:
        pl.when(_reached(row0, col0, bq, bk, window))(body)
    else:
        body()

    @pl.when(last)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, bq, bk, interpret, mxu, offsets, window, res, grads):
    q, k, v, out, lse, offs = res          # q, k, v as the kernels read them
    do, dlse = grads
    BH, Lq, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]
    nq, nk = Lq // bq, Lk // bk
    dtype = out.dtype                      # the caller's: dq, dk, dv leave in it
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # [BH, Lq]
    if dlse is not None:
        # d(lse)/dS = P, so an lse cotangent enters dS = P∘(dP - Δ + dlse)
        # — fold it into Δ rather than touching the kernels
        delta = delta - dlse.astype(jnp.float32)
    do = do.astype(mxu)                    # after Δ, which reads the f32
    # Δ stays a [BH, Lq] array of its own: left to fuse with what follows,
    # XLA:TPU turns the row sum and its broadcast into a reduce-window
    # 255 lanes wide over the whole [BH, Lq, 128] (PERF.md §6, PR 27)
    delta = jax.lax.optimization_barrier(delta)
    # the row statistics as ONE lane-padded array: lse in lane 0, Δ in lane 1
    lane = jax.lax.broadcasted_iota(jnp.int32, (BH, Lq, 128), 2)
    stats = jnp.where(lane == 0, lse[..., None], delta[..., None])

    keep = _causal_pairs(nq, nk, bq, bk, causal, offsets, window)

    def operands(at_q, at_k):
        return [pl.BlockSpec((1, bq, D), at_q),       # q
                pl.BlockSpec((1, bk, D), at_k),       # k
                pl.BlockSpec((1, bk, Dv), at_k),      # v
                pl.BlockSpec((1, bq, Dv), at_q),      # do
                pl.BlockSpec((1, bq, 128), at_q)]     # stats

    grid, tables, at_q, at_k = _grid(BH, nq, nk, keep)
    with jax.named_scope("pt.flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, mxu=mxu, listed=bool(tables),
                              window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1 + len(tables),
                grid=grid,
                in_specs=operands(at_q, at_k),
                out_specs=[pl.BlockSpec((1, bq, D), at_q)],
                scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            ),
            out_shape=[_out_struct((BH, Lq, D), dtype, q, k, v, do, offs)],
            compiler_params=_semantics(grid),
            name="flash_bwd_dq",
            interpret=interpret,
        )(offs, *tables, q, k, v, do, stats)[0]

    # swap block index roles: a run is one k block, walked over its q blocks
    grid, tables, at_q, at_k = _grid(BH, nq, nk, keep, k_major=True)
    with jax.named_scope("pt.flash_bwd_dkv"):
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              bq=bq, bk=bk, mxu=mxu, listed=bool(tables),
                              window=window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1 + len(tables),
                grid=grid,
                in_specs=operands(at_q, at_k),
                out_specs=[pl.BlockSpec((1, bk, D), at_k),
                           pl.BlockSpec((1, bk, Dv), at_k)],
                scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                                pltpu.VMEM((bk, Dv), jnp.float32)],
            ),
            out_shape=[_out_struct((BH, Lk, D), dtype, q, k, v, do, offs),
                       _out_struct((BH, Lk, Dv), dtype, q, k, v, do, offs)],
            compiler_params=_semantics(grid),
            name="flash_bwd_dkv",
            interpret=interpret,
        )(offs, *tables, q, k, v, do, stats)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 7, 8, 9, 10, 11, 12, 13))
def _flash(q, k, v, scale, causal, q_offset, k_offset, bq, bk, interpret,
           precision, dv, offsets, window):
    (out, _), _ = _flash_fwd(q, k, v, scale, causal, q_offset, k_offset,
                             bq, bk, interpret, precision, dv, offsets,
                             window)
    return out


def _mxu_dtype(precision):
    return jnp.float32 if precision == "highest" else jnp.bfloat16


@jax.custom_vjp
def mxu_rounded(x: jax.Array) -> jax.Array:
    """``x`` rounded to the dtype the kernels multiply in at the default
    precision, still in its own dtype; the cotangent passes through whole —
    what the kernels' custom VJP does to q, k and v anyway, so a call
    changes no value and no gradient. It is for the PRODUCER of q, k, v
    (``models/ernie.py``: the QKV matmul's output, under ``pt.attn``): the
    narrowing is then an op of the producer's scope that XLA fuses into
    the matmul, the kernels' operand convert cancels against the widening,
    and every layout op in between moves the narrow bytes under the
    producer's name. Left to itself XLA:TPU hoists the kernels' convert to
    the same place, but as an instruction of its own making, and the
    layout copy made from that carries no ``op_name`` (PERF.md §6, PR 27)."""
    # the round trip IS the op: the value is rounded where it is written,
    # and the widening cancels against the kernels' own narrowing
    return x.astype(_mxu_dtype("default")).astype(x.dtype)  # graftlint: ignore[cast-roundtrip]


mxu_rounded.defvjp(lambda x: (mxu_rounded(x), None), lambda _, g: (g,))


def _flash_fwd(q, k, v, scale, causal, q_offset, k_offset, bq, bk, interpret,
               precision, dv, offsets, window):
    mxu = _mxu_dtype(precision)
    nq, nk = q.shape[1] // bq, k.shape[1] // bk
    # `offsets`: the two offsets where they are Python ints (None where
    # they are data) — q_offset and k_offset themselves are tracers here
    keep = _causal_pairs(nq, nk, bq, bk, causal, offsets, window)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(k_offset, jnp.int32),
                      jnp.asarray(q.shape[1], jnp.int32),
                      jnp.asarray(k.shape[1], jnp.int32)])
    # The kernels multiply in `mxu`, so hand them `mxu`: the convert fuses
    # into the producer that writes each padded operand, and the residuals
    # are these. Inside the custom_vjp, so primals and cotangents keep the
    # caller's dtype (`out.dtype` carries it to the backward).
    dtype = q.dtype
    q, k, v = q.astype(mxu), k.astype(mxu), v.astype(mxu)
    # what the kernels are handed, read off the arrays themselves: one host
    # span a trace (``profiler.host_spans()``), none on the step path.
    # `scale` is 1/sqrt(D), all that is left here of the unpadded D; `dv`
    # is v's unpadded width (latent attention: q.k at 192, P.v at 128);
    # the block pairs a head's grid walks, of the rectangle's nq x nk;
    # the window's keys a query (0: none)
    with RecordEvent("pt.flash.operands", bits=8 * q.dtype.itemsize,
                     head_dim=round(scale ** -2), lanes=q.shape[-1],
                     v_head_dim=dv,
                     pairs_walked=nq * nk if keep is None else int(keep.sum()),
                     pairs_rectangle=nq * nk, window=window or 0):
        out, lse = _fwd(q, k, v, scale, causal, q_offset, k_offset, bq, bk,
                        interpret, mxu, dtype, keep, window)
    return (out, lse), (q, k, v, out, lse, offs)


def _flash_fwd_rule(q, k, v, scale, causal, q_offset, k_offset, bq, bk,
                    interpret, precision, dv, offsets, window):
    (out, lse), res = _flash_fwd(q, k, v, scale, causal, q_offset, k_offset,
                                 bq, bk, interpret, precision, dv, offsets,
                                 window)
    return out, (res, (q_offset, k_offset))


def _flash_bwd_rule(scale, causal, bq, bk, interpret, precision, dv, offsets,
                    window, saved, g):
    res, (q_offset, k_offset) = saved
    mxu = _mxu_dtype(precision)
    dq, dk, dv = _bwd(scale, causal, bq, bk, interpret, mxu, offsets, window,
                      res, (g, None))
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 7, 8, 9, 10, 11, 12, 13))
def _flash_pair(q, k, v, scale, causal, q_offset, k_offset, bq, bk,
                interpret, precision, dv, offsets, window):
    (out, lse), _ = _flash_fwd(q, k, v, scale, causal, q_offset, k_offset,
                               bq, bk, interpret, precision, dv, offsets,
                               window)
    return out, lse


def _flash_pair_fwd_rule(q, k, v, scale, causal, q_offset, k_offset, bq, bk,
                         interpret, precision, dv, offsets, window):
    (out, lse), res = _flash_fwd(q, k, v, scale, causal, q_offset, k_offset,
                                 bq, bk, interpret, precision, dv, offsets,
                                 window)
    return (out, lse), res


def _flash_pair_bwd_rule(scale, causal, bq, bk, interpret, precision, dv,
                         offsets, window, res, g):
    do, dlse = g
    mxu = _mxu_dtype(precision)
    dq, dk, dv = _bwd(scale, causal, bq, bk, interpret, mxu, offsets, window,
                      res, (do, dlse))
    return dq, dk, dv, None, None


_flash_pair.defvjp(_flash_pair_fwd_rule, _flash_pair_bwd_rule)


def flash_attention_with_lse(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False,
    q_offset=0, k_offset=0,
    block_q: int = 512, block_k: int = 512,
    interpret: Optional[bool] = None,
    precision: str = "default",
    window: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """flash attention returning (out, lse) — lse: [B, L, H] fp32.
    Differentiable in q/k/v including through lse (the cp ring merges
    per-device partials with lse weights, so its VJP needs dlse)."""
    out, lse, meta = _run_padded(q, k, v, causal, q_offset, k_offset,
                                 block_q, block_k, interpret, precision,
                                 window, with_lse=True)
    return out, lse


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False,
    q_offset=0, k_offset=0,
    block_q: int = 512, block_k: int = 512,
    interpret: Optional[bool] = None,
    precision: str = "default",
    window: Optional[int] = None,
) -> jax.Array:
    """Differentiable flash attention, [B, L, H, D] in and out. ``v`` may
    be narrower or wider than q and k (latent attention: q.k at 192, P.v
    at 128): the scale is q's ``1/sqrt(D)``, the result has v's width, and
    each width is padded to its own lane multiple — v is never padded to
    q's. ``window``: query ``i`` sees the keys ``i - window < j <= i`` (a
    sliding window; causal calls whose offsets are Python ints)."""
    out, _, _ = _run_padded(q, k, v, causal, q_offset, k_offset,
                            block_q, block_k, interpret, precision,
                            window, with_lse=False)
    return out


def _run_padded(q, k, v, causal, q_offset, k_offset, block_q, block_k,
                interpret, precision, window, with_lse):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Lq, H, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]
    scale = 1.0 / math.sqrt(D)
    bq = min(block_q, _round_up(Lq, 8))
    bk = min(block_k, _round_up(Lk, 8))
    Lq_p, Lk_p = _round_up(Lq, bq), _round_up(Lk, bk)

    # one dtype serves the three operands and every result: the widest, so
    # that nothing is rounded which the kernels would have read whole
    # (float32 k under ``precision="highest"`` beside a bf16 q); each
    # cotangent returns to its operand's own dtype through this convert
    wide = jnp.result_type(q, k, v)

    def to_bh(x, L, L_p):
        d = x.shape[-1]
        x = jnp.moveaxis(x, 2, 1).reshape(B * H, L, d).astype(wide)
        return jnp.pad(x, ((0, 0), (0, L_p - L), (0, -d % 128)))

    qp, kp, vp = to_bh(q, Lq, Lq_p), to_bh(k, Lk, Lk_p), to_bh(v, Lk, Lk_p)
    offsets = _static_offsets(q_offset, k_offset)
    if window is not None:
        enforce(isinstance(window, (int, np.integer)) and window >= 1,
                f"window {window!r}: a Python int of at least 1 key")
        enforce(causal and offsets is not None,
                "a window needs a causal call whose offsets are Python "
                "ints: which pairs a band leaves of a bidirectional call, "
                "or under traced offsets, is not guessed")
        # a window that reaches the first key from the last query is none
        if window > offsets[0] + Lq - 1 - offsets[1]:
            window = None

    if with_lse:
        out, lse = _flash_pair(qp, kp, vp, scale, causal, q_offset,
                               k_offset, bq, bk, interpret, precision, Dv,
                               offsets, window)
    else:
        out = _flash(qp, kp, vp, scale, causal, q_offset, k_offset, bq, bk,
                     interpret, precision, Dv, offsets, window)
        lse = None
    out = out[:, :Lq, :Dv].reshape(B, H, Lq, Dv).astype(q.dtype)
    out = jnp.moveaxis(out, 1, 2)
    if lse is not None:
        lse = lse[:, :Lq].reshape(B, H, Lq)
        lse = jnp.moveaxis(lse, 1, 2)          # [B, L, H]
    return out, lse, (bq, bk)

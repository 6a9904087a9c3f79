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

THE MASK IS STATED ONCE (PERF.md §6, PR 46). Which (row, key) products
exist is a ``Mask``: ``p <= r`` always, a sliding ``window`` or ``aligned``
windows where stated, over ``runs`` of key columns (``Keys``) — a run with
a ``stride`` where a column stands for several keys, ``earlier`` where it
holds the windows BEFORE the row's and not its own. ``causal=True`` is
``CAUSAL = Mask()``, ``window=w`` is ``Mask(window=w)``; EVA's two runs —
chunk summaries seen by later windows, then keys seen inside their own —
are ``ops/eva.eva_mask``. ``_reached`` turns the statement into a block's
bounds, and BOTH readers call it: ``_causal_pairs`` on numpy block numbers
at trace time (the walked list), every kernel on its grid step's scalars
(its ``pl.when`` and, through ``_seen``, its tile's mask). They cannot
disagree, and the three kernel bodies know no mask by name. A run of a
stated mask is padded to whole k blocks of its own, so a block lies in one
run and the run's parameters are scalars chosen by the block number.

A masked call whose offsets are Python ints walks a LIST of (q block,
k block) pairs, built with numpy at trace time: the pairs the mask leaves
something of, ``grid = (BH, pairs)``, the block numbers read from
scalar-prefetch tables. A grid step of the rectangle that the mask empties
still fetches its blocks and costs 0.6–1.0 µs (PERF.md §6, PR 41); at
L = 4096 in 512-blocks that is 28 of 64 a head, under a 4096-key band at
16,384 positions 776 of 1024, under EVA's mask at 8192 220 of 272. The
kernel bodies are the rectangle's own — the same mask, the same
``pl.when`` — so a walked pair does what its grid step did. Bidirectional
calls (no mask), traced offsets (``causal=True`` alone: the cp ring's) and
a call in which some block would have no pair keep the rectangle. A stated
mask refuses traced offsets and ``causal`` / ``window`` beside it
(``_stated_mask`` says why).

A window that reaches every key of the call is no window: ``window=None``
and such a call trace to the same program, the one a causal call always
had — as every call that existed before the statement does
(``tests/test_flash_attention.py`` pins their jaxprs).

Backward: standard flash backward — recompute P = exp(S - lse) blockwise;
dV = P^T dO, dS = P ∘ (dO V^T - Δ), dQ = dS K, dK = dS^T Q with
Δ = rowsum(dO ∘ O) computed outside (one fused elementwise pass).
"""

from __future__ import annotations

import functools
import itertools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.enforce import enforce
from ..core.profiler import RecordEvent

__all__ = ["flash_attention", "flash_attention_with_lse", "mxu_rounded",
           "Keys", "Mask", "CAUSAL"]

NEG = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _out_struct(shape, dtype, *inputs):
    """ShapeDtypeStruct carrying the union of the inputs' varying-manual-
    axes type — required for pallas_call under shard_map (check_vma)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# The mask a call states, and the block pairs it walks
# ---------------------------------------------------------------------------


def _static_offsets(q_offset, k_offset):
    """``(q_off, k_off)`` where both are known at trace time, else None.
    Read where the caller's values still are what they were: inside the
    ``custom_vjp``s a Python 0 is a tracer like the cp ring's
    ``axis_index``."""
    if all(isinstance(o, (int, np.integer)) for o in (q_offset, k_offset)):
        return int(q_offset), int(k_offset)
    return None


class Keys(NamedTuple):
    """A run of key columns. Column ``c`` of the run (counted from its
    first) stands at position ``stride·c + stride − 1`` — itself, or the
    last of the ``stride`` keys it summarises. ``earlier`` (under a mask's
    ``aligned`` windows): the run is seen from LATER windows — every key
    of the windows before the row's, no key of its own.
    ``count`` None: the keys of the call, whole (one run; positions then
    count from ``k_offset``, rows always from ``q_offset``)."""
    count: Optional[int] = None
    stride: int = 1
    earlier: bool = False


class Mask(NamedTuple):
    """Which (row, key) products exist. Row ``r`` sees the column at
    position ``p`` iff ``p <= r`` and, each where stated: ``p > r −
    window`` (a sliding band); with ``aligned``, rows and positions lying
    in aligned windows of that many, ``p`` in ``r``'s own window — or, in a
    run that is ``earlier``, in a window before it. ``runs``: the key axis
    cut into runs of columns, in order."""
    runs: Tuple[Keys, ...] = (Keys(),)
    window: Optional[int] = None
    aligned: Optional[int] = None


#: ``causal=True``: every key up to the row's own
CAUSAL = Mask()
#: past every position: the bound of a run that has none
_FAR = 2 ** 30


def _block_of_keys(mask, offsets, j, bk, where):
    """``(pos0, stride, limit, earlier)`` of k block ``j``: the position of
    its first column, the step to the next, the position past its run's
    last column (the keys of the call, whole: ``(k_off, k_len)``, summed
    where the tile is made), and whether its run holds the windows before
    the row's.
    Python numbers where every run agrees, else chosen by ``j`` with
    ``where`` (``jnp.where`` on a kernel's scalars, ``np.where`` on the
    block numbers of the pair list). A stated run is laid out from a block
    boundary (``_run_padded`` pads each to whole blocks)."""
    _, k_off, k_len = offsets
    runs = mask.runs
    if runs[0].count is None:
        return k_off + j * bk, 1, (k_off, k_len), runs[0].earlier
    starts = [0, *itertools.accumulate(-(-m.count // bk) for m in runs)]

    def pick(values):
        out = values[-1]
        for start, value in zip(starts[-2:0:-1], values[-2::-1]):
            out = out if value == out else where(j < start, value, out)
        return out

    start, stride = pick(starts[:-1]), pick([m.stride for m in runs])
    return ((j - start) * bk * stride + stride - 1, stride,
            pick([m.stride * m.count for m in runs]),
            pick([m.earlier for m in runs]))


class _Block(NamedTuple):
    """What a mask leaves of one block: ``some`` — whether anything (None:
    a bidirectional call, nothing to skip) — and the bounds its tile's
    mask is made from: row ``r`` (from ``row0``) sees the column at
    position ``p`` (from ``pos0``, ``stride`` apart) iff ``p < limit``,
    ``p <= r``, ``p > r − window`` and ``since <= p < before``, each bound
    None where the mask states none."""
    some: Any
    row0: Any
    pos0: Any
    stride: Any
    limit: Any
    window: Optional[int] = None
    since: Any = None
    before: Any = None


def _reached(mask, offsets, i, j, bq, bk, where=jnp.where):
    """The ``_Block`` of block ``(i, j)`` under ``mask``.
    ONE statement for the walked list (numpy block numbers, trace time)
    and for every kernel's ``pl.when`` and tile (its grid step's scalars):
    they cannot disagree. The rows of a q block share one aligned window
    (``_stated_mask`` enforces it), so its start is the block's."""
    row0 = offsets[0] + i * bq
    pos0, stride, limit, earlier = _block_of_keys(mask, offsets, j, bk, where)
    window, aligned = mask.window, mask.aligned
    some = row0 + bq - 1 >= pos0
    since = before = None
    if window is not None:
        some = some & (pos0 + bk * stride - stride > row0 - window)
    if aligned is not None:
        start = row0 - row0 % aligned
        if isinstance(earlier, bool):
            since, before = (None, start) if earlier else (start, None)
        else:
            since = where(earlier, -_FAR, start)
            before = where(earlier, start, _FAR)
        if since is not None:
            some = some & (pos0 + bk * stride - stride >= since)
        if before is not None:
            some = some & (pos0 < before)
    return _Block(some, row0, pos0, stride, limit, window, since, before)


def _causal_pairs(nq, nk, bq, bk, mask, offsets):
    """bool ``[nq, nk]``: the block pairs a call under ``mask`` walks —
    ``_reached`` of every block, the kernels' own ``pl.when``. For
    ``CAUSAL`` those with ``q_off + (i+1)·bq − 1 ≥ k_off + j·bk``; under a
    band those that also reach into it; under aligned windows a window's
    own lower triangle and, of the summaries, the blocks that hold a chunk
    of an earlier window. None where the call keeps the rectangle: it is
    bidirectional (``mask`` None), ``offsets`` is None (they are data), or
    some block would have no pair to name it — a q block before the first
    key, a k block past the last query's reach — whose zeros only the
    rectangle's first and last steps write."""
    if mask is None or offsets is None:
        return None
    keep = _reached(mask, offsets + (0,), np.arange(nq)[:, None],
                    np.arange(nk)[None, :], bq, bk, np.where).some
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


def _block(mask, offs_ref, i, j, bq, bk):
    """Inside a kernel: ``_reached`` of this grid step's block from the
    call's scalars; a bidirectional call (``mask`` None) has only the end
    of its keys to mask, and ``some`` None."""
    offsets = offs_ref[0], offs_ref[1], offs_ref[3]
    if mask is None:
        return _Block(None, offsets[0] + i * bq, offsets[1] + j * bk, 1,
                      offsets[1:])
    return _reached(mask, offsets, i, j, bq, bk)


def _seen(block, bq, bk):
    """Inside a kernel's body: bool ``[bq, bk]``, the products of this
    block that exist — its bounds over its rows and columns."""
    some, row0, pos0, stride, limit, window, since, before = block
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    # a Python 1 where every run's columns are keys: no multiply is traced
    cols = pos0 + (cols if isinstance(stride, int) and stride == 1
                   else cols * stride)
    mask = cols < (limit[0] + limit[1] if isinstance(limit, tuple) else limit)
    if some is None:                    # bidirectional: every key there is
        return mask
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    mask = mask & (cols <= rows)
    if window is not None:
        mask = mask & (cols > rows - window)
    if since is not None:
        mask = mask & (cols >= since)
    if before is not None:
        mask = mask & (cols < before)
    return mask


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


def _fwd_kernel(offs_ref, *refs, scale, mask, bq, bk, mxu, listed):
    (i, j, first, last), refs = _grid_step(refs, listed)
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr = refs
    # m_scr: the running max, replicated over its lanes; l_scr: the running
    # sum as lane-partial sums — both [bq, 128], read and written whole

    @pl.when(first)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    block = _block(mask, offs_ref, i, j, bq, bk)

    def body():
        # MXU operands in `mxu` dtype (bf16 default: single-pass MXU with
        # fp32 accumulation; fp32 operands = multi-pass, ~3x the cycles)
        q = q_ref[0].astype(mxu)          # [bq, D]
        k = k_ref[0].astype(mxu)          # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        s = jnp.where(_seen(block, bq, bk), s, NEG)

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

    if mask is not None:
        # block skip: nothing of the block is left → nothing to do
        pl.when(block.some)(body)
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


def _fwd(q, k, v, scale, mask, q_offset, k_offset, bq, bk, interpret, mxu,
         dtype, keep):
    BH, Lq, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]      # q.k at D, P.v and the result at Dv
    nq, nk = Lq // bq, Lk // bk
    offs = jnp.asarray(
        jnp.stack([jnp.asarray(q_offset, jnp.int32),
                   jnp.asarray(k_offset, jnp.int32),
                   jnp.asarray(Lq, jnp.int32),
                   jnp.asarray(k.shape[1], jnp.int32)]), jnp.int32)

    grid, tables, at_q, at_k = _grid(BH, nq, nk, keep)
    kernel = functools.partial(_fwd_kernel, scale=scale, mask=mask,
                               bq=bq, bk=bk, mxu=mxu, listed=bool(tables))
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


def _bwd_dq_kernel(offs_ref, *refs, scale, mask, bq, bk, mxu, listed):
    (i, j, first, last), refs = _grid_step(refs, listed)
    q_ref, k_ref, v_ref, do_ref, stats_ref, dq_ref, dq_acc = refs

    @pl.when(first)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    block = _block(mask, offs_ref, i, j, bq, bk)

    def body():
        q = q_ref[0].astype(mxu)
        k = k_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        seen = _seen(block, bq, bk)
        stats = stats_ref[0]
        lse, delta = stats[:, :1], stats[:, 1:2]
        p = jnp.where(seen & (lse > NEG / 2), jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do_ref[0].astype(mxu),
                                 v_ref[0].astype(mxu),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(ds.astype(mxu), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32) * scale

    if mask is not None:
        pl.when(block.some)(body)
    else:
        body()

    @pl.when(last)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, *refs, scale, mask, bq, bk, mxu, listed):
    # a run is one k block: the q blocks are the inner loop
    (i, j, first, last), refs = _grid_step(refs, listed, k_major=True)
    (q_ref, k_ref, v_ref, do_ref, stats_ref, dk_ref, dv_ref,
     dk_acc, dv_acc) = refs

    @pl.when(first)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    block = _block(mask, offs_ref, i, j, bq, bk)

    def body():
        q = q_ref[0].astype(mxu)
        k = k_ref[0].astype(mxu)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        seen = _seen(block, bq, bk)
        stats = stats_ref[0]
        lse, delta = stats[:, :1], stats[:, 1:2]
        p = jnp.where(seen & (lse > NEG / 2), jnp.exp(s - lse), 0.0)
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

    if mask is not None:
        pl.when(block.some)(body)
    else:
        body()

    @pl.when(last)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(scale, mask, bq, bk, interpret, mxu, offsets, res, grads):
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

    keep = _causal_pairs(nq, nk, bq, bk, mask, offsets)

    def operands(at_q, at_k):
        return [pl.BlockSpec((1, bq, D), at_q),       # q
                pl.BlockSpec((1, bk, D), at_k),       # k
                pl.BlockSpec((1, bk, Dv), at_k),      # v
                pl.BlockSpec((1, bq, Dv), at_q),      # do
                pl.BlockSpec((1, bq, 128), at_q)]     # stats

    grid, tables, at_q, at_k = _grid(BH, nq, nk, keep)
    with jax.named_scope("pt.flash_bwd_dq"):
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, mask=mask,
                              bq=bq, bk=bk, mxu=mxu, listed=bool(tables)),
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
            functools.partial(_bwd_dkv_kernel, scale=scale, mask=mask,
                              bq=bq, bk=bk, mxu=mxu, listed=bool(tables)),
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
                   nondiff_argnums=(3, 4, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, scale, mask, q_offset, k_offset, bq, bk, interpret,
           precision, dv, offsets):
    (out, _), _ = _flash_fwd(q, k, v, scale, mask, q_offset, k_offset,
                             bq, bk, interpret, precision, dv, offsets)
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


def _flash_fwd(q, k, v, scale, mask, q_offset, k_offset, bq, bk, interpret,
               precision, dv, offsets):
    mxu = _mxu_dtype(precision)
    nq, nk = q.shape[1] // bq, k.shape[1] // bk
    # `offsets`: the two offsets where they are Python ints (None where
    # they are data) — q_offset and k_offset themselves are tracers here
    keep = _causal_pairs(nq, nk, bq, bk, mask, offsets)
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
    # the window's keys a query (0: none); the key columns that stand for
    # more than one key each (chunk summaries; 0: none)
    runs = mask.runs if mask else ()
    with RecordEvent("pt.flash.operands", bits=8 * q.dtype.itemsize,
                     head_dim=round(scale ** -2), lanes=q.shape[-1],
                     v_head_dim=dv,
                     pairs_walked=nq * nk if keep is None else int(keep.sum()),
                     pairs_rectangle=nq * nk,
                     window=(mask.window if mask else None) or 0,
                     summary_keys=sum(m.count for m in runs if m.stride > 1)):
        out, lse = _fwd(q, k, v, scale, mask, q_offset, k_offset, bq, bk,
                        interpret, mxu, dtype, keep)
    return (out, lse), (q, k, v, out, lse, offs)


def _flash_fwd_rule(q, k, v, scale, mask, q_offset, k_offset, bq, bk,
                    interpret, precision, dv, offsets):
    (out, lse), res = _flash_fwd(q, k, v, scale, mask, q_offset, k_offset,
                                 bq, bk, interpret, precision, dv, offsets)
    return out, (res, (q_offset, k_offset))


def _flash_bwd_rule(scale, mask, bq, bk, interpret, precision, dv, offsets,
                    saved, g):
    res, (q_offset, k_offset) = saved
    mxu = _mxu_dtype(precision)
    dq, dk, dv = _bwd(scale, mask, bq, bk, interpret, mxu, offsets, res,
                      (g, None))
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 7, 8, 9, 10, 11, 12))
def _flash_pair(q, k, v, scale, mask, q_offset, k_offset, bq, bk,
                interpret, precision, dv, offsets):
    (out, lse), _ = _flash_fwd(q, k, v, scale, mask, q_offset, k_offset,
                               bq, bk, interpret, precision, dv, offsets)
    return out, lse


def _flash_pair_fwd_rule(q, k, v, scale, mask, q_offset, k_offset, bq, bk,
                         interpret, precision, dv, offsets):
    (out, lse), res = _flash_fwd(q, k, v, scale, mask, q_offset, k_offset,
                                 bq, bk, interpret, precision, dv, offsets)
    return (out, lse), res


def _flash_pair_bwd_rule(scale, mask, bq, bk, interpret, precision, dv,
                         offsets, res, g):
    do, dlse = g
    mxu = _mxu_dtype(precision)
    dq, dk, dv = _bwd(scale, mask, bq, bk, interpret, mxu, offsets, res,
                      (do, dlse))
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
    mask: Optional[Mask] = None,
) -> Tuple[jax.Array, jax.Array]:
    """flash attention returning (out, lse) — lse: [B, L, H] fp32.
    Differentiable in q/k/v including through lse (the cp ring merges
    per-device partials with lse weights, so its VJP needs dlse)."""
    out, lse, meta = _run_padded(q, k, v, causal, q_offset, k_offset,
                                 block_q, block_k, interpret, precision,
                                 window, mask, with_lse=True)
    return out, lse


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = False,
    q_offset=0, k_offset=0,
    block_q: int = 512, block_k: int = 512,
    interpret: Optional[bool] = None,
    precision: str = "default",
    window: Optional[int] = None,
    mask: Optional[Mask] = None,
) -> jax.Array:
    """Differentiable flash attention, [B, L, H, D] in and out. ``v`` may
    be narrower or wider than q and k (latent attention: q.k at 192, P.v
    at 128): the scale is q's ``1/sqrt(D)``, the result has v's width, and
    each width is padded to its own lane multiple — v is never padded to
    q's. ``window``: query ``i`` sees the keys ``i - window < j <= i`` (a
    sliding window; causal calls whose offsets are Python ints).
    ``mask``: the rule and the runs of key columns it holds over, stated
    (``Mask``); ``causal=True`` is ``mask=CAUSAL`` and ``window=w`` is
    ``Mask(window=w)``."""
    out, _, _ = _run_padded(q, k, v, causal, q_offset, k_offset,
                            block_q, block_k, interpret, precision,
                            window, mask, with_lse=False)
    return out


def _stated_mask(causal, window, mask, offsets, Lq, Lk, bq):
    """The call's mask as ONE statement (None: bidirectional), checked
    against what the pair list and the kernels' tile can be made from."""
    if mask is None:
        if window is None:
            return CAUSAL if causal else None
        enforce(isinstance(window, (int, np.integer)) and window >= 1,
                f"window {window!r}: a Python int of at least 1 key")
        enforce(causal and offsets is not None,
                "a window needs a causal call whose offsets are Python "
                "ints: which pairs a band leaves of a bidirectional call, "
                "or under traced offsets, is not guessed")
        # a window that reaches the first key from the last query is none
        if window > offsets[0] + Lq - 1 - offsets[1]:
            return CAUSAL
        return Mask(window=int(window))
    enforce(window is None and not causal,
            "a stated mask is the whole statement: it is causal by its "
            "rule, and a band is its `Mask(window=...)`")
    enforce(isinstance(mask, Mask) and len(mask.runs) >= 1
            and all(isinstance(m, Keys) for m in mask.runs),
            f"mask {mask!r}: a flash_attention.Mask over runs of "
            "flash_attention.Keys")
    enforce(offsets is not None,
            "a stated mask needs offsets that are Python ints: the pairs "
            "it leaves are listed at trace time (traced offsets, the cp "
            "ring's, take `causal=True` and the rectangle)")
    runs = mask.runs
    enforce(all(m.stride >= 1 for m in runs), "a column stands for its own "
            "key or for `stride` of them: at least 1")
    enforce(mask.aligned is not None or not any(m.earlier for m in runs),
            "`earlier` names the windows before the row's: it needs `aligned`")
    enforce(runs[0].count is not None or runs[0].stride == 1,
            "a run of summaries (`stride` over 1) states its `count`")
    if len(runs) > 1 or runs[0].count is not None:
        enforce(offsets == (0, 0) and all(m.count for m in runs)
                and sum(m.count for m in runs) == Lk,
                f"runs of {[m.count for m in runs]} columns over {Lk} keys "
                f"at offsets {offsets}: stated runs cover the keys exactly "
                "and count positions from 0")
    if mask.aligned is not None:
        enforce(offsets[0] % bq == 0 and mask.aligned % bq == 0,
                f"aligned windows of {mask.aligned} under q blocks of {bq} "
                f"from row {offsets[0]}: a q block's rows share one window")
    return mask


def _run_padded(q, k, v, causal, q_offset, k_offset, block_q, block_k,
                interpret, precision, window, mask, with_lse):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Lq, H, D = q.shape
    Lk, Dv = k.shape[1], v.shape[-1]
    scale = 1.0 / math.sqrt(D)
    offsets = _static_offsets(q_offset, k_offset)
    bq = min(block_q, _round_up(Lq, 8))
    mask = _stated_mask(causal, window, mask, offsets, Lq, Lk, bq)
    # the runs of the key axis, each padded to whole k blocks of its own
    runs = [Lk] if mask is None or mask.runs[0].count is None \
        else [m.count for m in mask.runs]
    bk = min(block_k, _round_up(max(runs), 8))
    Lq_p = _round_up(Lq, bq)

    # one dtype serves the three operands and every result: the widest, so
    # that nothing is rounded which the kernels would have read whole
    # (float32 k under ``precision="highest"`` beside a bf16 q); each
    # cotangent returns to its operand's own dtype through this convert
    wide = jnp.result_type(q, k, v)

    def to_bh(x, runs, block):
        d = x.shape[-1]
        x = jnp.moveaxis(x, 2, 1).reshape(B * H, -1, d).astype(wide)
        if len(runs) == 1:
            return jnp.pad(x, ((0, 0), (0, -runs[0] % block), (0, -d % 128)))
        return jnp.concatenate(
            [jnp.pad(x[:, end - n:end], ((0, 0), (0, -n % block),
                                         (0, -d % 128)))
             for n, end in zip(runs, itertools.accumulate(runs))], axis=1)

    qp, kp, vp = to_bh(q, [Lq], bq), to_bh(k, runs, bk), to_bh(v, runs, bk)

    if with_lse:
        out, lse = _flash_pair(qp, kp, vp, scale, mask, q_offset,
                               k_offset, bq, bk, interpret, precision, Dv,
                               offsets)
    else:
        out = _flash(qp, kp, vp, scale, mask, q_offset, k_offset, bq, bk,
                     interpret, precision, Dv, offsets)
        lse = None
    out = out[:, :Lq, :Dv].reshape(B, H, Lq, Dv).astype(q.dtype)
    out = jnp.moveaxis(out, 1, 2)
    if lse is not None:
        lse = lse[:, :Lq].reshape(B, H, Lq)
        lse = jnp.moveaxis(lse, 1, 2)          # [B, L, H]
    return out, lse, (bq, bk)

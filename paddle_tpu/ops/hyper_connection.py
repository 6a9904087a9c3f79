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
[2n + n²] vector, ``α`` three scalars, all learned, one set a sublayer. The
mappings are float32 whatever ``amp`` says: the projection is ONE
[tokens, nC] x [nC, 2n + n²] matmul at precision ``highest`` with the
norm's factor applied to its 2n + n² results (x̄ itself is never made), so
nothing of the size [tokens, nC, n²] exists.

Two forms of the same mathematics live here.

The plain definitions — ``hc_mappings`` (= the projection, then
``hc_gates``), ``hc_collect``, ``hc_scatter``, ``sinkhorn``,
``hc_res_err`` — are ``jnp``, differentiated by JAX; they read the streams
in their own dtype, add in float32 and leave ``u`` float32 and ``X'`` in
the streams' dtype. They are what the kernels are tested against.

The fused entry points are what ``models/transformer.HyperConnected``
runs: everything the size of a stream goes through four Pallas kernels over
token tiles (the streams as [tokens, n·C], stream j in columns jC..(j+1)C:
a tile's rows are whole (8, 128) registers of one stream), each reading and
writing every stream-sized operand once, with the backward stated
(``jax.custom_vjp``):

    hc_pre   -> hc_pre_fwd   scope pt.hc.collect   reads X; writes u, z,
                inv_rms (z = (vec(X) Φ) · inv_rms [tokens, 2n + n²], H_pre
                made of its first n in the tile) and hands X on unchanged
    hc_post  -> hc_post_fwd  scope pt.hc.scatter   reads X, y, H_post,
                H_res; writes X'
    backward:   hc_post_bwd  scope pt.hc.scatter   reads dX', X, y; writes
                dy, H_resᵀ dX' and the n² + n per-token products
                <dX'_i, X_j>, <dX'_i, y>
                hc_pre_bwd   scope pt.hc.collect   reads X, du, dz and the
                cotangent of the X handed on (= H_resᵀ dX': the streams'
                cotangent arrives in one piece); writes dX once, dΦ summed
                over the token grid in a resident block

14 stream-widths forward, 27 backward. What is [tokens, 2n + n²] — the
gates and biases, the sigmoids, the clamp, the ``lax.scan`` of Sinkhorn
steps, ``hc_res_err`` — is ``hc_gates`` on ``z``, plain ``jnp`` under the
caller's ``pt.hc.map``, differentiated by JAX through every step. The
kernels open their scopes themselves, forward and backward (an operation
belongs to the LAST ``pt.`` token of its name); off TPU they are
interpreted.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.enforce import enforce

__all__ = ["sinkhorn", "hc_mappings", "hc_gates", "hc_collect", "hc_scatter",
           "hc_res_err", "hc_pre", "hc_post"]


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


def _check(n: int, c: int, phi, b, alpha) -> None:
    enforce(n >= 2, f"hyper-connections over {n} stream: at least two (one "
            "stream is the plain residual block, which has no mapping)")
    enforce(phi.shape == (n * c, 2 * n + n * n) and b.shape == (phi.shape[1],)
            and alpha.shape == (3,),
            f"mappings of {n} streams of {c}: phi {phi.shape}, b {b.shape}, "
            f"alpha {alpha.shape}")


def hc_gates(z: jax.Array, b: jax.Array, alpha: jax.Array, n: int,
             iters: int, eps: float, clamp: Tuple[float, float]
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` of the
    normed streams' projection ``z`` [..., 2n + n²], float32: everything
    of the mappings that is no wider than ``z``. ``clamp`` bounds ``H̃_res``
    before the ``exp``."""
    # alpha_pre on the first n results, alpha_post on the next n, alpha_res
    # on the n x n that follow
    gate = alpha.astype(jnp.float32)[np.repeat(np.arange(3), (n, n, n * n))]
    z = z * gate + b.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(z[..., :n])
    h_post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    res = z[..., 2 * n:].reshape(*z.shape[:-1], n, n)
    return h_pre, h_post, sinkhorn(jnp.clip(res, clamp[0], clamp[1]),
                                   iters, eps)


def hc_mappings(x: jax.Array, phi: jax.Array, b: jax.Array, alpha: jax.Array,
                iters: int, eps: float, clamp: Tuple[float, float],
                rms_eps: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(H_pre, H_post, H_res)`` of the streams ``x`` [..., n, C],
    float32: the norm, the projection, ``hc_gates``."""
    n, c = x.shape[-2:]
    _check(n, c, phi, b, alpha)
    flat = x.reshape(*x.shape[:-2], n * c).astype(jnp.float32)
    inv_rms = lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                        + rms_eps)
    z = jnp.dot(flat, phi.astype(jnp.float32),
                precision=lax.Precision.HIGHEST) * inv_rms
    return hc_gates(z, b, alpha, n, iters, eps, clamp)


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


# ---------------------------------------------------------------------------
# The four kernels
# ---------------------------------------------------------------------------
#
# Inside a kernel a token tile is walked eight rows at a time (one sublane
# group of float32; sixteen where a stream is 16 bits wide), and those rows
# 128 lanes at a time, so that what is live between two steps is registers:
# the per-token coefficients broadcast across lanes once a group, one
# register of every stream operand, and the running per-token products.

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
#: bytes of the streams' tiles one grid step holds (each is held twice:
#: the pipeline fetches the next while this one is worked on), and what a
#: kernel may take of the core's VMEM in all — the tile, its second copy,
#: Phi and the projection's temporaries (a v5e core has 128 MiB). At the
#: cell's widths 10 MiB is 128 / 64 / 32 / 32 tokens a step for the four
#: kernels; 20 and 40 MiB ran them no faster on the chip and took Mosaic
#: longer to compile, a tile's projection being unrolled (PERF.md, PR 52)
_TILE_BYTES = 10 * 2**20
_VMEM_LIMIT = 64 * 2**20


def _group_rows(*dtypes) -> int:
    """Rows of one walk step: a whole sublane group of the narrowest
    stream."""
    return 8 * 4 // min(jnp.dtype(d).itemsize for d in dtypes)


def _token_tile(tokens: int, rows: int, row_bytes: int) -> int:
    """Tokens a grid step: as many whole row groups as ``_TILE_BYTES``
    holds of the ``row_bytes`` a token's tiled operands take, at most 256
    (past that a step's DMA is long enough to hide what a step costs), at
    least one group, and no more than the tokens there are; where a count
    of at least half of that divides the tokens, that one, so that no tile
    is a partial one."""
    t = max(rows, min(256, _TILE_BYTES // row_bytes) // rows * rows)
    t = min(t, -(-tokens // rows) * rows)
    whole = [d for d in range(t, t // 2, -rows) if tokens % d == 0]
    return whole[0] if whole else t


def _lane_width(c: int, most: int = 128) -> int:
    """Lanes of one walk step: a register's 128 — or, for a step that does
    little with each (``most`` 512), as many registers' as divide the
    stream — or the whole stream where it is no multiple of 128 (small
    test widths)."""
    return next((w for w in (512, 256, 128) if w <= most and c % w == 0), c)


def _over_lanes(columns: int, width: int, body, carry=None, turn: int = 4):
    """``carry = body(at, carry)`` for every piece of ``width`` lanes of
    ``columns``, ``at`` its first column: a loop in the kernel (at most
    ``turn`` pieces a turn), not ``columns / width`` copies of ``body`` in
    it — a kernel's text is then much the same size at any width, and so
    is what a program pays to trace, lower, compile and read it back: with
    every piece written out the cell's warm set-up took 24 s longer than
    the parent's 41 (PERF.md, PR 52)."""
    count = columns // width
    turn = next(k for k in range(min(turn, count), 0, -1) if count % k == 0)

    def pieces(k, carry):
        for q in range(turn):
            carry = body(pl.multiple_of((k * turn + q) * width, width), carry)
        return carry

    return lax.fori_loop(0, count // turn, pieces, carry)


def _piece(ref, r, at, width):
    """Rows ``r``, columns ``at``.. of a stream operand, float32."""
    return ref[r, pl.ds(at, width)].astype(_F32)


def _col(v, k, width):
    """Column ``k`` of ``v`` [rows, .] across ``width`` lanes."""
    return jnp.broadcast_to(v[:, k:k + 1], (v.shape[0], width))


def _scatter_cols(cols, width):
    """[rows, width] whose column k is ``cols[k]`` [rows, 1] (zero past
    them): a select a column on the lane index — no lane-wise concatenate
    of one-lane pieces."""
    rows = cols[0].shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.zeros((rows, width), _F32)
    for k, col in enumerate(cols):
        out = jnp.where(lane == k, col, out)
    return out


def _row_sum(v):
    return jnp.sum(v, axis=-1, keepdims=True)


def _h_pre(z, gate_ref):
    """``H_pre`` in the first n columns of [rows, 2n + n²] (``_gate_rows``
    has zeros past them: 0.5 there, which nothing reads)."""
    return jax.nn.sigmoid(z * gate_ref[0:1, :] + gate_ref[1:2, :])


def _groups(t: int, rows: int, body) -> None:
    def step(g, carry):
        body(pl.ds(pl.multiple_of(g * rows, rows), rows))
        return carry

    lax.fori_loop(0, t // rows, step, None)


def _pre_fwd_kernel(x_ref, phit_ref, gate_ref, u_ref, z_ref, inv_ref, *,
                    n, c, rms_eps, rows):
    # raw = vec(X) Phi for the whole tile on the MXU, float32 in six bf16
    # passes; the norm's factor goes onto its results below
    z_ref[...] = lax.dot_general(
        x_ref[...].astype(_F32), phit_ref[...], (((1,), (1,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=_F32)
    width = _lane_width(c, 512)

    def group(r):
        def square(at, ss):
            v = _piece(x_ref, r, at, width)
            return ss + v * v

        ss = _over_lanes(n * c, width, square,
                         jnp.zeros((rows, width), _F32), turn=7)
        inv = lax.rsqrt(_row_sum(ss) / (n * c) + rms_eps)
        z = z_ref[r, :] * inv
        z_ref[r, :] = z
        inv_ref[r, :] = inv
        h = _h_pre(z, gate_ref)
        hb = [_col(h, j, width) for j in range(n)]

        def collect(at, _):
            u_ref[r, pl.ds(at, width)] = sum(
                hb[j] * _piece(x_ref, r, j * c + at, width) for j in range(n))

        _over_lanes(c, width, collect, turn=7)

    _groups(x_ref.shape[0], rows, group)


def _mappings(h_ref, r, n, width):
    """``(H_post[i], H_res[i][j])`` of rows ``r``, each across ``width``
    lanes, from [H_post | H_res row by row]."""
    h = h_ref[r, :]
    return ([_col(h, i, width) for i in range(n)],
            [[_col(h, n + i * n + j, width) for j in range(n)]
             for i in range(n)])


def _post_fwd_kernel(x_ref, y_ref, h_ref, o_ref, *, n, c, rows):
    width = _lane_width(c)

    def group(r):
        post, res = _mappings(h_ref, r, n, width)

        def scatter(at, _):
            y = _piece(y_ref, r, at, width)
            xs = [_piece(x_ref, r, j * c + at, width) for j in range(n)]
            for i in range(n):
                mixed = sum(res[i][j] * xs[j] for j in range(n))
                o_ref[r, pl.ds(i * c + at, width)] = (
                    mixed + post[i] * y).astype(o_ref.dtype)

        _over_lanes(c, width, scatter)

    _groups(x_ref.shape[0], rows, group)


def _post_bwd_kernel(g_ref, x_ref, y_ref, h_ref, dx_ref, dy_ref, dh_ref, *,
                     n, c, rows):
    width = _lane_width(c)

    def group(r):
        post, res = _mappings(h_ref, r, n, width)

        def back(at, products):
            d_post, d_res = products
            y = _piece(y_ref, r, at, width)
            gs = [_piece(g_ref, r, i * c + at, width) for i in range(n)]
            xs = [_piece(x_ref, r, j * c + at, width) for j in range(n)]
            dy_ref[r, pl.ds(at, width)] = sum(
                post[i] * gs[i] for i in range(n)).astype(dy_ref.dtype)
            for j in range(n):              # H_res transposed
                dx_ref[r, pl.ds(j * c + at, width)] = sum(
                    res[i][j] * gs[i] for i in range(n)).astype(dx_ref.dtype)
            return ([d_post[i] + gs[i] * y for i in range(n)],
                    [[d_res[i][j] + gs[i] * xs[j] for j in range(n)]
                     for i in range(n)])

        # <dX'_i, y> and <dX'_i, X_j>, lane by lane until the row sums
        zero = jnp.zeros((rows, width), _F32)
        d_post, d_res = _over_lanes(
            c, width, back, ([zero] * n, [[zero] * n for _ in range(n)]))
        cols = [_row_sum(v) for v in d_post + sum(d_res, [])]
        dh_ref[r, :] = _scatter_cols(cols, dh_ref.shape[1])

    _groups(x_ref.shape[0], rows, group)


def _pre_bwd_kernel(x_ref, du_ref, dxr_ref, dz_ref, z_ref, inv_ref, phit_ref,
                    gate_ref, dx_ref, g_ref, dphit_ref, dzs_ref, coef_ref,
                    *scratch, n, c, rows, tokens):
    t, m = z_ref.shape
    width = _lane_width(c, 512)
    step = pl.program_id(0)
    # the projection's cotangent in float32: dX's own block where the
    # streams are float32, a scratch where they are narrower
    acc_ref = scratch[0] if scratch else dx_ref

    def head(r):
        # dH_pre[j] = <du, X_j> into dz's first n columns through the
        # sigmoid and alpha_pre; then what the norm's factor takes
        def dot(at, dots):
            du = _piece(du_ref, r, at, width)
            return [dots[j] + du * _piece(x_ref, r, j * c + at, width)
                    for j in range(n)]

        dots = _over_lanes(c, width, dot,
                           [jnp.zeros((rows, width), _F32)] * n, turn=7)
        z, inv = z_ref[r, :], inv_ref[r, :]
        h = _h_pre(z, gate_ref)
        g = _scatter_cols([_row_sum(d) for d in dots], m) * h * (1.0 - h)
        dz = dz_ref[r, :] + gate_ref[0:1, :] * g
        g_ref[r, :] = g
        dzs_ref[r, :] = dz * inv
        coef_ref[r, :] = inv * inv * _row_sum(dz * z) / (n * c)

    _groups(t, rows, head)
    dzs, x = dzs_ref[...], x_ref[...].astype(_F32)
    if tokens % t:      # the last tile's rows past the tokens are no one's
        live = lax.broadcasted_iota(jnp.int32, (t, 1), 0) < tokens - step * t
        dzs, x = jnp.where(live, dzs, 0.0), jnp.where(live, x, 0.0)
    acc_ref[...] = lax.dot_general(
        dzs, phit_ref[...], (((1,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=_F32)
    dphit = lax.dot_general(
        dzs, x, (((0,), (0,)), ((), ())),
        precision=_HIGHEST, preferred_element_type=_F32)

    @pl.when(step == 0)
    def _():
        dphit_ref[...] = dphit

    @pl.when(step > 0)
    def _():
        dphit_ref[...] += dphit

    def tail(r):
        h = _h_pre(z_ref[r, :], gate_ref)
        hb = [_col(h, j, width) for j in range(n)]
        coef = jnp.broadcast_to(coef_ref[r, :], (rows, width))

        def add(at, _):
            du = _piece(du_ref, r, at, width)
            for j in range(n):
                here = pl.ds(j * c + at, width)
                dx_ref[r, here] = (
                    acc_ref[r, here] + dxr_ref[r, here].astype(_F32)
                    - coef * x_ref[r, here].astype(_F32)
                    + hb[j] * du).astype(dx_ref.dtype)

        _over_lanes(c, width, add, turn=7)

    _groups(t, rows, tail)


def _params(sequential: bool = False):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary" if sequential else "parallel",),
        vmem_limit_bytes=_VMEM_LIMIT)


def _tiled(t, width):
    """A token tile of a [tokens, width] operand."""
    return pl.BlockSpec((t, width), lambda i: (i, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0, 0))


def _gate_rows(b, alpha, n):
    """[2, 2n + n²]: alpha_pre over the first n columns, b_pre under it,
    zeros past them (what a kernel needs to make H_pre of z)."""
    m = b.shape[0]
    lead = jnp.arange(m) < n
    return jnp.stack([jnp.where(lead, alpha[0], 0.0),
                      jnp.where(lead, b, 0.0)]).astype(_F32)


# Each kernel's call is a jitted function of its own: a model's sublayers
# (ten of them forward, rebuilt and backward in a step, and again in every
# other program of a process) then share ONE trace of the kernel's body and
# ONE lowering a program, where each ``pallas_call`` met anew is walked 28
# lane pieces x n streams at a time — 18 s of the 22.6 s the cell's float32
# function took to lower (CPU, PR 52). ``interpret`` is an argument, so
# that what was traced for the CPU is not what a TPU is handed.
_kernel_call = functools.partial(jax.jit, static_argnames=("n", "interpret"))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("n", "rms_eps", "interpret"))
def _pre_fwd_call(x, phi, b, alpha, *, n, rms_eps, interpret):
    tokens, nc = x.shape
    c, m = nc // n, phi.shape[1]
    rows = _group_rows(x.dtype)
    t = _token_tile(tokens, rows, nc * x.dtype.itemsize + c * 4)
    with jax.named_scope("pt.hc.collect"):
        return pl.pallas_call(
            functools.partial(_pre_fwd_kernel, n=n, c=c, rms_eps=rms_eps,
                              rows=rows),
            grid=(pl.cdiv(tokens, t),),
            in_specs=[_tiled(t, nc), _whole((m, nc)), _whole((2, m))],
            out_specs=[_tiled(t, c), _tiled(t, m), _tiled(t, 1)],
            out_shape=[jax.ShapeDtypeStruct((tokens, c), _F32),
                       jax.ShapeDtypeStruct((tokens, m), _F32),
                       jax.ShapeDtypeStruct((tokens, 1), _F32)],
            compiler_params=_params(), name="hc_pre_fwd",
            interpret=interpret,
        )(x, phi.astype(_F32).T, _gate_rows(b, alpha, n))


@_kernel_call
def _pre_bwd_call(x, phi, b, alpha, z, inv, du, dz, dxr, *, n, interpret):
    tokens, nc = x.shape
    c, m = nc // n, phi.shape[1]
    rows = _group_rows(x.dtype, du.dtype, dxr.dtype)
    wide = x.dtype != _F32
    t = _token_tile(tokens, rows, nc * (3 * x.dtype.itemsize
                                        + (4 if wide else 0)) + c * 4)
    with jax.named_scope("pt.hc.collect"):
        dx, g, dphit = pl.pallas_call(
            functools.partial(_pre_bwd_kernel, n=n, c=c, rows=rows,
                              tokens=tokens),
            grid=(pl.cdiv(tokens, t),),
            in_specs=[_tiled(t, nc), _tiled(t, c), _tiled(t, nc), _tiled(t, m),
                      _tiled(t, m), _tiled(t, 1), _whole((m, nc)),
                      _whole((2, m))],
            out_specs=[_tiled(t, nc), _tiled(t, m), _whole((m, nc))],
            out_shape=[jax.ShapeDtypeStruct((tokens, nc), x.dtype),
                       jax.ShapeDtypeStruct((tokens, m), _F32),
                       jax.ShapeDtypeStruct((m, nc), _F32)],
            scratch_shapes=[pltpu.VMEM((t, m), _F32), pltpu.VMEM((t, 1), _F32)]
            + ([pltpu.VMEM((t, nc), _F32)] if wide else []),
            compiler_params=_params(sequential=True), name="hc_pre_bwd",
            interpret=interpret,
        )(x, du, dxr, dz.astype(_F32), z, inv, phi.astype(_F32).T,
          _gate_rows(b, alpha, n))
        # g = dH̃_pre in its n columns: b_pre's cotangent summed over the
        # tokens, alpha_pre's against z
        dalpha = jnp.zeros((3,), _F32).at[0].set(jnp.sum(g * z))
        return (dx, dphit.T.astype(phi.dtype),
                jnp.sum(g, axis=0).astype(b.dtype), dalpha.astype(alpha.dtype))


@_kernel_call
def _post_fwd_call(x, y, h, *, n, interpret):
    tokens, nc = x.shape
    rows = _group_rows(x.dtype, y.dtype)
    t = _token_tile(tokens, rows, 2 * nc * x.dtype.itemsize
                    + nc // n * y.dtype.itemsize)
    with jax.named_scope("pt.hc.scatter"):
        return pl.pallas_call(
            functools.partial(_post_fwd_kernel, n=n, c=nc // n, rows=rows),
            grid=(pl.cdiv(tokens, t),),
            in_specs=[_tiled(t, nc), _tiled(t, nc // n), _tiled(t, h.shape[1])],
            out_specs=_tiled(t, nc),
            out_shape=jax.ShapeDtypeStruct((tokens, nc), x.dtype),
            compiler_params=_params(), name="hc_post_fwd",
            interpret=interpret,
        )(x, y, h)


@_kernel_call
def _post_bwd_call(g, x, y, h, *, n, interpret):
    tokens, nc = x.shape
    c = nc // n
    rows = _group_rows(x.dtype, y.dtype, g.dtype)
    t = _token_tile(tokens, rows, 3 * nc * x.dtype.itemsize
                    + 2 * c * y.dtype.itemsize)
    with jax.named_scope("pt.hc.scatter"):
        dx, dy, dh = pl.pallas_call(
            functools.partial(_post_bwd_kernel, n=n, c=c, rows=rows),
            grid=(pl.cdiv(tokens, t),),
            in_specs=[_tiled(t, nc), _tiled(t, nc), _tiled(t, c),
                      _tiled(t, h.shape[1])],
            out_specs=[_tiled(t, nc), _tiled(t, c), _tiled(t, h.shape[1])],
            out_shape=[jax.ShapeDtypeStruct((tokens, nc), x.dtype),
                       jax.ShapeDtypeStruct((tokens, c), y.dtype),
                       jax.ShapeDtypeStruct(h.shape, _F32)],
            compiler_params=_params(), name="hc_post_bwd",
            interpret=interpret,
        )(g, x, y, h)
        return dx, dy, dh.astype(h.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pre(x, phi, b, alpha, n, rms_eps):
    return _pre_fwd(x, phi, b, alpha, n, rms_eps)[0]


def _pre_fwd(x, phi, b, alpha, n, rms_eps):
    u, z, inv = _pre_fwd_call(x, phi, b, alpha, n=n, rms_eps=rms_eps,
                              interpret=_interpret())
    return (u, z, x), (x, phi, b, alpha, z, inv)


def _pre_bwd(n, rms_eps, res, cts):
    return _pre_bwd_call(*res, *cts, n=n, interpret=_interpret())


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _post(x, y, h, n):
    return _post_fwd(x, y, h, n)[0]


def _post_fwd(x, y, h, n):
    return _post_fwd_call(x, y, h, n=n, interpret=_interpret()), (x, y, h)


def _post_bwd(n, res, g):
    return _post_bwd_call(g, *res, n=n, interpret=_interpret())


_post.defvjp(_post_fwd, _post_bwd)


def hc_pre(x: jax.Array, phi: jax.Array, b: jax.Array, alpha: jax.Array,
           rms_eps: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The streams ``x`` [..., n, C] read once: ``(u, z, x)`` with ``u =
    H_pre X`` [..., C] float32 (``hc_collect`` of ``hc_mappings``' first),
    ``z`` [..., 2n + n²] float32 the normed streams' projection
    (``hc_gates`` makes the mappings of it) and ``x`` itself, handed on for
    ``hc_post`` to read: the backward then meets the streams' whole
    cotangent in one kernel and writes it once. Kernels ``hc_pre_fwd`` /
    ``hc_pre_bwd``, scope ``pt.hc.collect``."""
    n, c = x.shape[-2:]
    _check(n, c, phi, b, alpha)
    lead = x.shape[:-2]
    u, z, flat = _pre(x.reshape(-1, n * c), phi, b, alpha, n, float(rms_eps))
    return (u.reshape(*lead, c), z.reshape(*lead, z.shape[-1]),
            flat.reshape(x.shape))


def hc_post(x: jax.Array, y: jax.Array, h_post: jax.Array,
            h_res: jax.Array) -> jax.Array:
    """``hc_scatter`` in one pass: ``X' = H_res X + H_postᵀ y`` [..., n, C]
    in ``x``'s dtype, the sums float32. Kernels ``hc_post_fwd`` /
    ``hc_post_bwd``, scope ``pt.hc.scatter``."""
    n, c = x.shape[-2:]
    with jax.named_scope("pt.hc.scatter"):
        h = jnp.concatenate(
            [h_post, h_res.reshape(*h_res.shape[:-2], n * n)],
            axis=-1).astype(_F32).reshape(-1, n + n * n)
    return _post(x.reshape(-1, n * c), y.reshape(-1, c), h, n).reshape(x.shape)

"""Pallas flash attention: fwd/bwd parity vs the einsum reference
(interpret mode on CPU; the same kernels compile on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops.flash_attention import (flash_attention,
                                            flash_attention_with_lse)
from paddle_tpu.parallel.ring_attention import local_attention


def _qkv(rng, B=2, L=64, H=2, D=16):
    mk = lambda: jnp.asarray(rng.normal(size=(B, L, H, D)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_fwd_matches_reference(causal):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng)
    ref = local_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True, precision="highest")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, B=1, L=32, H=2, D=8)

    def ref_loss(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=causal) ** 2)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                              interpret=True, precision="highest")
        return jnp.sum(out ** 2)

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=name)


def test_unaligned_shapes_padded():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, B=1, L=50, H=3, D=12)
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                          interpret=True, precision="highest")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_offsets_shift_causal_mask():
    """q_offset/k_offset reproduce a cp shard's causal mask: rows of the
    second half attending over the full sequence."""
    rng = np.random.default_rng(3)
    B, L, H, D = 1, 32, 2, 8
    q, k, v = _qkv(rng, B=B, L=L, H=H, D=D)
    full = local_attention(q, k, v, causal=True)
    # shard: second half of queries vs first half of keys (fully visible)
    q2 = q[:, L // 2:]
    out_lo, lse_lo = flash_attention_with_lse(
        q2, k[:, :L // 2], v[:, :L // 2], causal=True,
        q_offset=L // 2, k_offset=0, block_q=16, block_k=16, interpret=True, precision="highest")
    out_hi, lse_hi = flash_attention_with_lse(
        q2, k[:, L // 2:], v[:, L // 2:], causal=True,
        q_offset=L // 2, k_offset=L // 2, block_q=16, block_k=16,
        interpret=True, precision="highest")
    # lse-merge the two halves (the ring-attention combine)
    m = jnp.maximum(lse_lo, lse_hi)
    w_lo = jnp.exp(lse_lo - m)[..., None]
    w_hi = jnp.exp(lse_hi - m)[..., None]
    merged = (out_lo * w_lo + out_hi * w_hi) / (w_lo + w_hi)
    np.testing.assert_allclose(np.asarray(merged),
                               np.asarray(full[:, L // 2:]),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="pallas interpret mode under shard_map lacks vma "
                           "propagation (jax hlo_interpreter dynamic_slice); "
                           "compiled mosaic path is exercised on TPU")
@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_serial(causal):
    """Flash-kernel ring over a cp mesh == full attention (interpret mode)."""
    import os
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core import mesh as mesh_mod
    from paddle_tpu.parallel.ring_attention import ring_flash_attention

    rng = np.random.default_rng(4)
    B, L, H, D = 1, 32, 2, 8
    q, k, v = _qkv(rng, B=B, L=L, H=H, D=D)
    full = local_attention(q, k, v, causal=causal)
    mesh = mesh_mod.make_mesh({"dp": 2, "cp": 4})

    def f(q, k, v):
        return ring_flash_attention(q, k, v, axis="cp", causal=causal)

    spec = P(None, "cp", None, None)
    out = shard_map(f, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-2, atol=2e-3)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="see test_ring_flash_matches_serial")
def test_ring_flash_grads_finite():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core import mesh as mesh_mod
    from paddle_tpu.parallel.ring_attention import ring_flash_attention

    rng = np.random.default_rng(5)
    B, L, H, D = 1, 32, 2, 8
    q, k, v = _qkv(rng, B=B, L=L, H=H, D=D)
    mesh = mesh_mod.make_mesh({"dp": 2, "cp": 4})
    spec = P(None, "cp", None, None)

    def loss(q, k, v):
        def f(q, k, v):
            out = ring_flash_attention(q, k, v, axis="cp", causal=True)
            return jax.lax.psum(jnp.sum(out ** 2), "cp")
        return shard_map(f, mesh=mesh, in_specs=(spec,) * 3, out_specs=P())(q, k, v)

    # parity oracle: einsum ring == flash ring gradients
    def loss_ref(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g, g_ref, "qkv"):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-3, err_msg=name)


# ---------------------------------------------------------------------------
# what the kernels are handed (PR 27): operands in the dtype they multiply
# in, one row-statistics array in the backward
# ---------------------------------------------------------------------------


def _out_and_grads(q, k, v, causal, precision="default"):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16,
                              interpret=True, precision=precision)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


def _kernel_operands(fn, *args):
    """{kernel name: [(dtype, shape) of each array operand]} of every
    ``pallas_call`` in the jaxpr of ``fn(*args)`` (the scalar-prefetch
    vector left out)."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = [
                    (v.aval.dtype, tuple(v.aval.shape))
                    for v in eqn.invars if v.aval.ndim == 3]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("causal", [False, True])
def test_f32_inputs_equal_their_bf16_rounding_bit_for_bit(causal):
    """The kernels multiply in bf16, so handing them bf16 only moves the
    rounding from the kernel's first line to its producer's last: float32
    inputs give, bit for bit, what the same inputs rounded to bf16 and
    widened back give — and every result is still float32."""
    rng = np.random.default_rng(6)
    qkv = _qkv(rng, B=1, L=40, H=3, D=8)
    rounded = [x.astype(jnp.bfloat16).astype(jnp.float32) for x in qkv]
    assert any(not np.array_equal(a, b) for a, b in zip(qkv, rounded))
    got = _out_and_grads(*qkv, causal)
    want = _out_and_grads(*rounded, causal)
    for a, b, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert a.dtype == jnp.float32, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def _close(a, b, rtol, atol, name):
    """``atol`` as a share of the reference's largest entry."""
    b = np.asarray(b, np.float32)
    np.testing.assert_allclose(np.asarray(a, np.float32), b, rtol=rtol,
                               atol=atol * float(np.abs(b).max()),
                               err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_default_precision_is_the_reference_on_rounded_operands(causal):
    """What the rounding is pinned to: float32 inputs at the default
    precision give what the einsum reference gives on the inputs ROUNDED
    to bf16 — out at the tolerance the default-precision ring test holds
    (the reference on the inputs as they were is 3 to 8 times as far), the
    gradients at that of the ring's (the kernels round P, dS and dO)."""
    rng = np.random.default_rng(10)
    qkv = _qkv(rng, B=1, L=48, H=3, D=8)
    rounded = [x.astype(jnp.bfloat16).astype(jnp.float32) for x in qkv]

    def ref_loss(q, k, v):
        out = local_attention(q, k, v, causal=causal)
        return jnp.sum(out ** 2), out

    (_, out), grads = jax.value_and_grad(ref_loss, argnums=(0, 1, 2),
                                         has_aux=True)(*rounded)
    got = _out_and_grads(*qkv, causal)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(out),
                               rtol=2e-2, atol=2e-3, err_msg="out")
    for a, b, name in zip(got[1:], grads, ("dq", "dk", "dv")):
        _close(a, b, 5e-2, 5e-3, name)


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_mixed_dtypes_keep_the_widest(precision):
    """bf16 q beside float32 k and v: the operands meet in float32, so
    under ``precision="highest"`` k and v reach the kernels whole; out
    leaves in q's dtype and each gradient in its operand's own — and the
    values are those of the all-float32 call on the same numbers."""
    rng = np.random.default_rng(11)
    q, k, v = _qkv(rng, B=1, L=32, H=2, D=8)
    qb = q.astype(jnp.bfloat16)
    got = _out_and_grads(qb, k, v, True, precision)
    assert [a.dtype for a in got] == [jnp.bfloat16, jnp.bfloat16,
                                      jnp.float32, jnp.float32]
    ops = _kernel_operands(
        lambda q, k, v: _out_and_grads(q, k, v, True, precision), qb, k, v)
    operand = jnp.float32 if precision == "highest" else jnp.bfloat16
    assert ops["flash_fwd"] == [(operand, (2, 32, 128))] * 3
    want = _out_and_grads(qb.astype(jnp.float32), k, v, True, precision)
    np.testing.assert_array_equal(
        np.asarray(got[0]), np.asarray(want[0].astype(jnp.bfloat16)))
    for a, b, name in zip(got[1:], want[1:], ("dq", "dk", "dv")):
        _close(a, b, 5e-2, 5e-3, name)    # dO comes from the bf16 out


def test_mxu_rounded_changes_no_value_and_no_gradient():
    """``mxu_rounded`` at the producer of q, k, v is the kernels' own
    rounding done early: the attention of the rounded operands and its
    gradients — through the rounding, which passes the cotangent whole —
    are bit-equal to those of the operands as they were."""
    from paddle_tpu.ops.flash_attention import mxu_rounded

    rng = np.random.default_rng(12)
    qkv = _qkv(rng, B=1, L=32, H=2, D=8)
    assert not np.array_equal(mxu_rounded(qkv[0]), qkv[0])
    assert mxu_rounded(qkv[0]).dtype == jnp.float32

    def loss(q, k, v):
        out = flash_attention(mxu_rounded(q), mxu_rounded(k), mxu_rounded(v),
                              causal=True, block_q=16, block_k=16,
                              interpret=True)
        return jnp.sum(out ** 2), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(*qkv)
    for a, b, name in zip((out,) + grads, _out_and_grads(*qkv, True),
                          ("out", "dq", "dk", "dv")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_bf16_inputs_get_bf16_back():
    rng = np.random.default_rng(7)
    qkv = [x.astype(jnp.bfloat16) for x in _qkv(rng, B=1, L=32, H=2, D=8)]
    for a in _out_and_grads(*qkv, causal=True):
        assert a.dtype == jnp.bfloat16 and np.isfinite(
            np.asarray(a, np.float32)).all()


@pytest.mark.parametrize("precision,operand",
                         [("default", jnp.bfloat16), ("highest", jnp.float32)])
def test_kernel_operands_are_the_dtype_they_multiply_in(precision, operand):
    """q, k, v (and do in the backward) reach each kernel in the MXU
    dtype ``precision`` decides — float32 under "highest", where nothing
    is rounded — and the two backward kernels read lse and delta from ONE
    float32 ``[BH, L, 128]`` array."""
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, B=1, L=32, H=2, D=8)
    ops = _kernel_operands(
        lambda q, k, v: _out_and_grads(q, k, v, True, precision), q, k, v)
    assert set(ops) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    wide = (2, 32, 128)
    assert ops["flash_fwd"] == [(operand, wide)] * 3
    for name in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert ops[name] == [(operand, wide)] * 4 + [(jnp.float32, wide)], name


@pytest.mark.parametrize("causal,walked", [(False, 4), (True, 3)])
@pytest.mark.parametrize("precision,bits", [("default", 16), ("highest", 32)])
def test_operand_span_is_recorded_once_a_compile(precision, bits, causal,
                                                 walked):
    """``pt.flash.operands``: one host span a trace with the width of the
    arrays the forward kernel was handed and the block pairs a head's grid
    walks of the rectangle's (PR 41: 2 x 2 blocks, the causal list leaves
    one out), none on the step path."""
    from paddle_tpu.core import profiler

    rng = np.random.default_rng(9)
    q, k, v = _qkv(rng, B=1, L=32, H=2, D=8)
    step = jax.jit(lambda q, k, v: _out_and_grads(q, k, v, causal, precision))
    spans = lambda: [s.counts for s in profiler.host_spans()
                     if s.name == "pt.flash.operands"]
    before = len(spans())
    for _ in range(3):
        jax.block_until_ready(step(q, k, v))
    assert spans()[before:] == [{"bits": bits, "head_dim": 8, "lanes": 128,
                                 "v_head_dim": 8, "pairs_walked": walked,
                                 "pairs_rectangle": 4, "window": 0,
                                 "summary_keys": 0}]


# ---------------------------------------------------------------------------
# the block pairs a causal call walks (PR 41): a list built at trace time,
# against the same list holding the whole rectangle and against the
# rectangle's own three-dimensional grid
# ---------------------------------------------------------------------------

#: the TPU interpreter with scratch, outputs and every VMEM window filled
#: with NaN before the kernel writes them: a block read before it is
#: written, or never written, fails here as it would on the chip
NAN_FILLED = pltpu.InterpretParams(uninitialized_memory="nan")


def _whole_rectangle(*a):
    """``_causal_pairs`` answering with every pair: the rectangle walked
    as a list, the emptied pairs skipped by the body's own ``pl.when``."""
    keep = _PAIRS(*a)
    return keep if keep is None else np.ones_like(keep)


_PAIRS = fa._causal_pairs
_WALKS = {"list": _PAIRS, "rectangle as a list": _whole_rectangle,
          "the parent's grid": lambda *a: None}


def _results(monkeypatch, walk, q, k, v, **kw):
    """{name: array} of out, lse, dq, dk, dv of one causal call with an
    lse cotangent, under ``walk`` of ``_WALKS``, NaN-filled memory."""
    monkeypatch.setattr(fa, "_causal_pairs", _WALKS[walk])
    w = jnp.cos(jnp.arange(q.shape[1] * q.shape[2], dtype=jnp.float32)
                ).reshape(1, q.shape[1], q.shape[2])

    def loss(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, causal=True,
                                            interpret=NAN_FILLED, **kw)
        return jnp.sum(out ** 2) + jnp.sum(lse * w), (out, lse)

    (_, aux), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                     has_aux=True)(q, k, v)
    return dict(zip(("out", "lse", "dq", "dk", "dv"), aux + g))


def _grids(fn, *args):
    """{kernel name: its grid} of every ``pallas_call`` in ``fn``'s jaxpr."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# (B, Lq, Lk, H, D), blocks, offsets, pairs walked of the rectangle's —
# every q block and k block is named (one q block has to name them all)
LISTED = {
    "bq256_bk512": ((1, 1024, 1024, 1, 8), (256, 512), (0, 0), (6, 8)),
    "bq512_bk256": ((1, 1024, 1024, 1, 8), (512, 256), (0, 0), (6, 8)),
    "q_off_over_k_off": ((1, 64, 64, 2, 8), (16, 16), (32, 0), (15, 16)),
    "q_off_is_k_off": ((1, 64, 64, 2, 8), (16, 16), (16, 16), (10, 16)),
    "bh1": ((1, 64, 64, 1, 8), (16, 16), (0, 0), (10, 16)),
    "lq_one_block": ((2, 16, 64, 2, 8), (16, 16), (48, 0), (4, 4)),
    "unaligned_lengths": ((1, 50, 50, 3, 12), (16, 16), (0, 0), (10, 16)),
}


@pytest.mark.parametrize("case", sorted(LISTED))
def test_pair_list_equals_the_rectangle_bit_for_bit(monkeypatch, case):
    """The triangular list gives, bit for bit, what the full-rectangle
    list gives and what the parent's ``(BH, nq, nk)`` grid gives — out,
    lse, dq, dk, dv — with every scratch and output block NaN until a
    kernel writes it."""
    (B, Lq, Lk, H, D), (bq, bk), (q_off, k_off), walked = LISTED[case]
    rng = np.random.default_rng(20)
    q = jnp.asarray(rng.normal(size=(B, Lq, H, D)).astype(np.float32))
    k, v = (jnp.asarray(rng.normal(size=(B, Lk, H, D)).astype(np.float32))
            for _ in range(2))
    kw = dict(q_offset=q_off, k_offset=k_off, block_q=bq, block_k=bk)
    got = {walk: _results(monkeypatch, walk, q, k, v, **kw)
           for walk in _WALKS}
    monkeypatch.setattr(fa, "_causal_pairs", _PAIRS)
    nq, nk = -(-Lq // bq), -(-Lk // bk)
    keep = _PAIRS(nq, nk, min(bq, Lq), min(bk, Lk), fa.CAUSAL,
                  (q_off, k_off))
    assert (int(keep.sum()), keep.size) == walked
    for name, want in got["the parent's grid"].items():
        assert np.isfinite(np.asarray(want)).all(), name
        for walk in ("list", "rectangle as a list"):
            np.testing.assert_array_equal(
                np.asarray(got[walk][name]), np.asarray(want),
                err_msg=f"{name}: {walk}")


def test_forward_alone_walks_the_list_and_equals_the_rectangle(monkeypatch):
    """``flash_attention`` outside a gradient — the ``routing`` program's
    path: the forward kernel alone, no residual kept."""
    rng = np.random.default_rng(21)
    q, k, v = _qkv(rng, B=2, L=64, H=2, D=8)
    out = {}
    for walk, pairs in _WALKS.items():
        monkeypatch.setattr(fa, "_causal_pairs", pairs)
        fn = lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            interpret=NAN_FILLED)
        out[walk] = np.asarray(fn(q, k, v))
        grid = _grids(fn, q, k, v)
        assert set(grid) == {"flash_fwd"}
        assert grid["flash_fwd"] == {"list": (4, 10),
                                     "rectangle as a list": (4, 16),
                                     "the parent's grid": (4, 4, 4)}[walk]
    assert np.isfinite(out["list"]).all()
    np.testing.assert_array_equal(out["list"], out["the parent's grid"])
    np.testing.assert_array_equal(out["rectangle as a list"],
                                  out["the parent's grid"])


@pytest.mark.parametrize("nq,nk,bq,bk,offsets", [
    (8, 8, 512, 512, (0, 0)), (16, 8, 256, 512, (0, 0)),
    (8, 16, 512, 256, (0, 0)), (4, 4, 16, 16, (32, 0)),
    (4, 4, 16, 16, (16, 16)), (1, 4, 16, 16, (48, 0)), (1, 1, 8, 8, (0, 0)),
    (3, 5, 24, 8, (7, 0))])
@pytest.mark.parametrize("k_major", [False, True])
def test_pair_tables_name_every_block_in_contiguous_runs(
        nq, nk, bq, bk, offsets, k_major):
    """The tables' own properties: the pairs are exactly the kernels'
    ``pl.when``; every q block and every k block is named; a run (one q
    block, or with ``k_major`` one k block) is contiguous and ascending,
    flagged first once and last once."""
    keep = fa._causal_pairs(nq, nk, bq, bk, fa.CAUSAL, offsets)
    q_off, k_off = offsets
    want = {(i, j) for i in range(nq) for j in range(nk)
            if q_off + (i + 1) * bq - 1 >= k_off + j * bk}
    qi, kj, ends = (np.asarray(t) for t in fa._pair_tables(keep, k_major))
    assert all(t.dtype == np.int32 for t in (qi, kj, ends))
    pairs = list(zip(qi.tolist(), kj.tolist()))
    assert set(pairs) == want and len(pairs) == len(want)
    assert set(qi.tolist()) == set(range(nq))
    assert set(kj.tolist()) == set(range(nk))
    run, inner = (kj, qi) if k_major else (qi, kj)
    order = list(zip(run.tolist(), inner.tolist()))
    assert order == sorted(order)               # runs ascending, contiguous
    first, last = (ends & 1) != 0, (ends & 2) != 0
    starts = np.r_[True, run[1:] != run[:-1]]
    stops = np.r_[run[1:] != run[:-1], True]
    np.testing.assert_array_equal(first, starts)
    np.testing.assert_array_equal(last, stops)
    assert first.sum() == last.sum() == len(set(run.tolist()))


def test_the_cells_calls_walk_36_of_64_pairs():
    keep = fa._causal_pairs(8, 8, 512, 512, fa.CAUSAL, (0, 0))
    assert int(keep.sum()) == 36 and keep.size == 64
    assert fa._causal_pairs(8, 8, 512, 512, None, (0, 0)) is None
    assert fa._causal_pairs(8, 8, 512, 512, fa.CAUSAL, None) is None
    assert fa._static_offsets(0, 0) == (0, 0)
    assert fa._static_offsets(np.int32(3), 0) == (3, 0)
    assert fa._static_offsets(jnp.int32(0), 0) is None      # data


@pytest.mark.parametrize("case,shape,offsets", [
    ("q block before the first key", (1, 64, 64, 2, 8), (0, 32)),
    ("k blocks past the last query", (1, 32, 64, 2, 8), (0, 0))])
def test_a_call_with_an_unnamed_block_takes_the_rectangle(
        case, shape, offsets):
    """Where some q block has no key to see, or some k block no query that
    reaches it, no pair would name that block and nothing would write it:
    the call keeps the rectangle, whose first and last steps write the
    zeros — dk and dv of an unreachable k block are exact zeros, under
    NaN-filled memory too."""
    B, Lq, Lk, H, D = shape
    q_off, k_off = offsets
    assert fa._causal_pairs(Lq // 16, Lk // 16, 16, 16, fa.CAUSAL, offsets) is None
    rng = np.random.default_rng(22)
    q = jnp.asarray(rng.normal(size=(B, Lq, H, D)).astype(np.float32))
    k, v = (jnp.asarray(rng.normal(size=(B, Lk, H, D)).astype(np.float32))
            for _ in range(2))

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, q_offset=q_off, k_offset=k_off,
            block_q=16, block_k=16, interpret=NAN_FILLED,
            precision="highest") ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    assert all(len(g) == 3 for g in _grids(fwd_bwd, q, k, v).values())
    dq, dk, dv = (np.asarray(g) for g in fwd_bwd(q, k, v))
    assert all(np.isfinite(g).all() for g in (dq, dk, dv))
    # the reference: masked einsum attention at the same offsets
    rows = q_off + np.arange(Lq)[:, None]
    cols = k_off + np.arange(Lk)[None, :]
    mask = jnp.asarray(cols <= rows)

    def ref_loss(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision="highest") / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        p = jnp.where(mask.any(axis=1)[:, None], p, 0.0)   # rows that see none
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", p, v,
                                  precision="highest") ** 2)

    for a, b, name in zip((dq, dk, dv), jax.grad(
            ref_loss, argnums=(0, 1, 2))(q, k, v), ("dq", "dk", "dv")):
        np.testing.assert_allclose(a, np.asarray(b), rtol=2e-3, atol=2e-4,
                                   err_msg=name)
    unseen = ~np.asarray(mask).any(axis=0)             # keys no query sees
    if case.startswith("k blocks"):
        assert unseen[32:].all()
    assert (dk[:, unseen] == 0).all() and (dv[:, unseen] == 0).all()


def test_only_causal_calls_with_int_offsets_walk_a_list():
    """What the code can observe decides the grid: ``causal`` and whether
    the offsets are Python ints. A bidirectional call and a call whose
    offsets are data (the cp ring's ``axis_index``) lower to the parent's
    three-dimensional grid, forward and backward."""
    rng = np.random.default_rng(23)
    q, k, v = _qkv(rng, B=1, L=64, H=2, D=8)

    def fwd_bwd(causal, q_off, k_off):
        loss = lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, q_offset=q_off, k_offset=k_off,
            block_q=16, block_k=16, interpret=True) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    names = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    listed = _grids(lambda: fwd_bwd(True, 0, 0))
    assert listed == dict.fromkeys(names, (2, 10))
    assert _grids(lambda: fwd_bwd(False, 0, 0)) == {
        "flash_fwd": (2, 4, 4), "flash_bwd_dq": (2, 4, 4),
        "flash_bwd_dkv": (2, 4, 4)}
    traced = _grids(lambda o: fwd_bwd(True, o, o), jnp.int32(0))
    assert traced == dict.fromkeys(names, (2, 4, 4))
    # and the traced-offset call gives what the list gives at the same offsets
    for a, b in zip(jax.jit(lambda o: fwd_bwd(True, o, o))(jnp.int32(0)),
                    fwd_bwd(True, 0, 0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_nan_filled_memory_shows_a_block_no_pair_names(monkeypatch):
    """The instrument sees the fault it is there for: a list that leaves a
    q block out leaves that block of the output unwritten — NaN under
    ``NAN_FILLED``, where the plain interpreter reads zeros."""
    def one_block_short(*a):
        keep = _PAIRS(*a).copy()
        keep[1] = False
        return keep

    monkeypatch.setattr(fa, "_causal_pairs", one_block_short)
    rng = np.random.default_rng(24)
    q, k, v = _qkv(rng, B=1, L=64, H=1, D=8)
    out = np.asarray(flash_attention(q, k, v, causal=True, block_q=16,
                                     block_k=16, interpret=NAN_FILLED))
    assert np.isnan(out[:, 16:32]).all()
    assert np.isfinite(out[:, :16]).all() and np.isfinite(out[:, 32:]).all()


# ---------------------------------------------------------------------------
# a sliding window (PR 44): a second bound on the pair list and a second
# term of the kernels' mask, against the einsum under the same mask
# ---------------------------------------------------------------------------


def _banded_reference(q, k, v, window, q_off=0, k_off=0):
    """Einsum attention in which query ``i`` sees the keys ``i - window <
    j <= i`` (positions counted from the two offsets)."""
    return local_attention(q, k, v, causal=True, q_offset=q_off,
                           k_offset=k_off, window=window)


_WINDOWED = {}


def _windowed_case(window):
    """{name: (kernels' array, einsum's)} of out, dq, dk, dv of one
    windowed call on 64 positions in 16-blocks, NaN-filled memory: the
    three kernels under one weighted loss."""
    if window not in _WINDOWED:
        rng = np.random.default_rng(31)
        q, k, v = _qkv(rng, B=1, L=64, H=2, D=8)
        w = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
        kernels = lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, block_q=16, block_k=16,
            interpret=NAN_FILLED, precision="highest")
        einsum = lambda q, k, v: _banded_reference(q, k, v, window)
        got, want = [
            (f(q, k, v),) + jax.grad(lambda *a: jnp.sum(f(*a) * w),
                                     argnums=(0, 1, 2))(q, k, v)
            for f in (kernels, einsum)]
        _WINDOWED[window] = dict(zip(("out", "dq", "dk", "dv"),
                                     zip(got, want)))
    return _WINDOWED[window]


# shorter than a block, a block, one more, no block multiple, the
# sequence less one, the sequence, past it
@pytest.mark.parametrize("window", [1, 5, 16, 17, 40, 63, 64, 100])
@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
def test_window_matches_the_einsum_mask(window, what):
    got, want = _windowed_case(window)[what]
    assert np.isfinite(np.asarray(got)).all()       # every block written
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=3e-6)


@pytest.mark.parametrize("window,offsets,Lk", [
    (8, (16, 0), 32), (24, (32, 16), 32), (8, (48, 0), 64)])
def test_window_counts_positions_from_the_offsets(window, offsets, Lk):
    """Python-int offsets shift the band as they shift the diagonal; the
    last case leaves k blocks below every query's band, so no pair would
    name them and the call keeps the rectangle, whose body skips what the
    band empties and whose last step writes their zeros."""
    rng = np.random.default_rng(33)
    q_off, k_off = offsets
    q = jnp.asarray(rng.normal(size=(1, 16, 2, 8)).astype(np.float32))
    k, v = (jnp.asarray(rng.normal(size=(1, Lk, 2, 8)).astype(np.float32))
            for _ in range(2))
    listed = fa._causal_pairs(1, Lk // 16, 16, 16, fa.Mask(window=window),
                               offsets)
    assert (listed is None) == (offsets == (48, 0))

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)

    kernels = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, q_offset=q_off, k_offset=k_off,
        block_q=16, block_k=16, interpret=NAN_FILLED, precision="highest")
    einsum = lambda q, k, v: _banded_reference(q, k, v, window, q_off, k_off)
    np.testing.assert_allclose(np.asarray(kernels(q, k, v)),
                               np.asarray(einsum(q, k, v)), atol=3e-6)
    for a, b in zip(jax.grad(loss(kernels), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(einsum), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_the_windowed_cells_calls_walk_252_of_1024_pairs():
    """16,384 positions in 512-blocks: the causal list is 528 of the 32 x
    32 rectangle; a 4096-key window leaves i + 1 pairs in each of the
    first 8 block rows and 9 in each of the other 24 (the ninth is the
    block the band's lower edge crosses)."""
    causal = fa._causal_pairs(32, 32, 512, 512, fa.CAUSAL, (0, 0))
    banded = fa._causal_pairs(32, 32, 512, 512, fa.Mask(window=4096),
                              (0, 0))
    assert int(causal.sum()) == 528
    assert int(banded.sum()) == 252 == sum(range(1, 9)) + 24 * 9
    assert not (banded & ~causal).any()
    assert banded.sum(axis=1).tolist() == list(range(1, 9)) + [9] * 24
    # a window of one more key reaches a tenth block from a block's first row
    assert int(fa._causal_pairs(32, 32, 512, 512, fa.Mask(window=4097),
                                (0, 0)).sum()) == 252
    assert int(fa._causal_pairs(32, 32, 512, 512, fa.Mask(window=4098),
                                (0, 0)).sum()) == 252 + 23
    for k_major in (False, True):
        qi, kj, ends = fa._pair_tables(banded, k_major)
        assert len(qi) == 252 and int((np.asarray(ends) & 1).sum()) == 32


@pytest.mark.parametrize("window", [64, 65, 1000])
def test_a_window_that_reaches_every_key_is_no_window(window):
    """``window >= L`` traces to the program ``window=None`` traces to —
    the same jaxpr, kernels and all — and the span says 0."""
    from paddle_tpu.core import profiler

    rng = np.random.default_rng(34)
    q, k, v = _qkv(rng, B=1, L=64, H=2, D=8)

    def traced(window):
        f = lambda q, k, v: jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, window=window, block_q=16, block_k=16,
            interpret=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        return str(jax.make_jaxpr(f)(q, k, v))

    spans = lambda: [s.counts["window"] for s in profiler.host_spans()
                     if s.name == "pt.flash.operands"]
    before = len(spans())
    assert traced(window) == traced(None)
    assert traced(63) != traced(None)
    assert spans()[before:] == [0, 0, 63, 0]


@pytest.mark.parametrize("case", ["bidirectional", "traced offset",
                                  "no key", "not an int"])
def test_a_window_that_would_be_guessed_is_refused(case):
    from paddle_tpu.core.enforce import EnforceNotMet

    rng = np.random.default_rng(35)
    q, k, v = _qkv(rng, B=1, L=32, H=1, D=8)
    kw = {"bidirectional": dict(causal=False, window=8),
          "traced offset": dict(causal=True, window=8),
          "no key": dict(causal=True, window=0),
          "not an int": dict(causal=True, window=8.0)}[case]
    call = lambda o: flash_attention(q, k, v, q_offset=o, interpret=True,
                                     **kw)
    with pytest.raises(EnforceNotMet):
        if case == "traced offset":
            jax.jit(call)(jnp.int32(0))
        else:
            call(0)


# ---------------------------------------------------------------------------
# the forward's lane-wide row statistics (PR 45): the running max replicated
# over 128 lanes, the running sum as lane-partial sums reduced once a run —
# at blocks of whole 128-key lane groups, against the plain softmax and
# against the same call in blocks under 128 keys, where the row sum is taken
# across lanes every pair as the step before PR 45 took it
# ---------------------------------------------------------------------------


def _softmax_reference(q, k, v, causal, window=None, q_off=0, k_off=0):
    """(out, lse) of plain ``jax.numpy`` softmax attention, [B, L, H, ·]
    and [B, L, H]; v may have a width of its own."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    if causal:
        rows = q_off + np.arange(q.shape[1])[:, None]
        cols = k_off + np.arange(k.shape[1])[None, :]
        seen = cols <= rows
        if window is not None:
            seen &= cols > rows - window
        s = jnp.where(jnp.asarray(seen), s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v,
                     precision="highest")
    return out, jnp.moveaxis(lse, 1, 2)


# (B, Lq, Lk, H, D, Dv), (block_q, block_k), keywords of the call
LANE_WIDE = {
    "causal_listed": ((1, 512, 512, 2, 16, 16), (128, 128),
                      dict(causal=True)),
    # rows 355.. of the second q block meet keys 128..255 wholly below
    # their band first: their max is still NEG when the step ends
    "window_edge_inside_a_block": ((1, 512, 512, 2, 16, 16), (256, 128),
                                   dict(causal=True, window=100)),
    "bidirectional_one_pair_a_step": ((2, 256, 256, 2, 16, 16), (256, 256),
                                      dict(causal=False)),
    "v_narrower_than_qk": ((1, 512, 512, 2, 24, 16), (128, 256),
                           dict(causal=True)),
    "unaligned_lengths": ((1, 300, 300, 3, 12, 12), (128, 128),
                          dict(causal=True)),
    "traced_offsets_rectangle": ((1, 256, 256, 2, 16, 16), (128, 128),
                                 dict(causal=True, traced=True)),
    "the_cells_blocks": ((1, 1024, 1024, 1, 8, 8), (512, 512),
                         dict(causal=True)),
}


def _lane_wide_operands(case):
    (B, Lq, Lk, H, D, Dv), blocks, kw = LANE_WIDE[case]
    rng = np.random.default_rng(45)
    mk = lambda *shape: jnp.asarray(rng.normal(size=shape).astype(np.float32))
    return (mk(B, Lq, H, D), mk(B, Lk, H, D), mk(B, Lk, H, Dv)), blocks, \
        dict(kw)


def _lane_wide_call(qkv, blocks, kw, precision, with_lse=True):
    kw = dict(kw, block_q=blocks[0], block_k=blocks[1],
              interpret=NAN_FILLED, precision=precision)
    fn = flash_attention_with_lse if with_lse else flash_attention
    if kw.pop("traced", False):
        return jax.jit(lambda o: fn(*qkv, q_offset=o, k_offset=o, **kw))(
            jnp.int32(0))
    return fn(*qkv, **kw)


@pytest.mark.parametrize("precision", ["highest", "default"])
@pytest.mark.parametrize("case", sorted(LANE_WIDE))
def test_lane_wide_statistics_match_the_plain_softmax(case, precision):
    """out and lse, every scratch and window NaN until the kernel writes
    it: float32 operands under "highest" to 2e-6 of the plain softmax AND
    of the same call in 64-key blocks (the row sum across lanes every
    pair); at the default precision to the file's bf16 limits of the
    reference on the operands rounded to bf16."""
    qkv, blocks, kw = _lane_wide_operands(case)
    out, lse = _lane_wide_call(qkv, blocks, kw, precision)
    assert lse.shape == qkv[0].shape[:3] and lse.dtype == jnp.float32
    ref_kw = {k: v for k, v in kw.items() if k != "traced"}
    if precision == "highest":
        want = _softmax_reference(*qkv, **ref_kw)
        narrow = _lane_wide_call(qkv, (blocks[0], 64), kw, precision)
        for got, a, b, name in zip((out, lse), want, narrow, ("out", "lse")):
            assert np.isfinite(np.asarray(got)).all(), name
            np.testing.assert_allclose(np.asarray(got), np.asarray(a),
                                       rtol=0, atol=2e-6, err_msg=name)
            np.testing.assert_allclose(np.asarray(got), np.asarray(b),
                                       rtol=0, atol=2e-6,
                                       err_msg=name + " in 64-key blocks")
    else:
        rounded = [x.astype(jnp.bfloat16).astype(jnp.float32) for x in qkv]
        want = _softmax_reference(*rounded, **ref_kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want[0]),
                                   rtol=2e-2, atol=2e-3, err_msg="out")
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want[1]),
                                   rtol=2e-2, atol=2e-3, err_msg="lse")


def test_lane_wide_forward_alone_outside_a_gradient():
    """``flash_attention`` with no gradient round it (the ``routing``
    program's path) gives the out of the pair's forward, bit for bit."""
    qkv, blocks, kw = _lane_wide_operands("window_edge_inside_a_block")
    alone = _lane_wide_call(qkv, blocks, kw, "default", with_lse=False)
    out, _ = _lane_wide_call(qkv, blocks, kw, "default")
    assert np.isfinite(np.asarray(alone)).all()
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(out))


def test_lane_wide_rows_that_meet_no_key_stay_empty():
    """A q block before the first key (the rectangle: no pair would name
    it): its rows never leave ``m = NEG``, every exponent is taken from 0,
    and the last step writes zeros and ``lse = NEG`` — at 128-key blocks,
    under NaN-filled memory."""
    rng = np.random.default_rng(46)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 256, 2, 16)).astype(
        np.float32)) for _ in range(3))
    out, lse = flash_attention_with_lse(
        q, k, v, causal=True, q_offset=0, k_offset=128, block_q=128,
        block_k=128, interpret=NAN_FILLED, precision="highest")
    assert (np.asarray(out[:, :128]) == 0).all()
    assert (np.asarray(lse[:, :128]) == fa.NEG).all()
    want, want_lse = _softmax_reference(q[:, 128:], k[:, :128], v[:, :128],
                                        True)
    np.testing.assert_allclose(np.asarray(out[:, 128:]), np.asarray(want),
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(lse[:, 128:]),
                               np.asarray(want_lse), rtol=0, atol=2e-6)


@pytest.mark.parametrize("case", ["causal_listed",
                                  "window_edge_inside_a_block",
                                  "v_narrower_than_qk"])
def test_grads_through_the_lane_wide_forward_match_reference(case):
    """The backward kernels read the forward's lse: dq, dk, dv of a loss
    on out AND lse against the plain softmax's, at
    ``test_grads_match_reference``'s limits."""
    qkv, blocks, kw = _lane_wide_operands(case)
    w = jnp.cos(jnp.arange(qkv[0].shape[1] * qkv[0].shape[2],
                           dtype=jnp.float32)).reshape(
        1, qkv[0].shape[1], qkv[0].shape[2])

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out ** 2) + jnp.sum(lse * w)
        return f

    kernels = lambda *a: _lane_wide_call(a, blocks, kw, "highest")
    plain = lambda *a: _softmax_reference(*a, **kw)
    for a, b, name in zip(jax.grad(loss(kernels), argnums=(0, 1, 2))(*qkv),
                          jax.grad(loss(plain), argnums=(0, 1, 2))(*qkv),
                          ("dq", "dk", "dv")):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=name)


def test_lane_sums_and_across_keep_a_rows_sum_and_a_rows_statistic():
    """``_lane_sums``: 128 lane-partial sums whose sum is the row's, the
    lane groups added in order; the row sum in lane 0 where the width is
    no lane multiple. ``_across``: the same 128 lanes beside every lane
    group; one column where the width is no lane multiple."""
    rng = np.random.default_rng(47)
    p = jnp.asarray(rng.uniform(size=(8, 384)).astype(np.float32))
    part = fa._lane_sums(p)
    assert part.shape == (8, 128)
    np.testing.assert_array_equal(
        np.asarray(part), np.asarray((p[:, :128] + p[:, 128:256])
                                     + p[:, 256:]))
    narrow = fa._lane_sums(p[:, :24])
    assert narrow.shape == (8, 128) and not np.asarray(narrow[:, 1:]).any()
    np.testing.assert_array_equal(np.asarray(narrow[:, 0]),
                                  np.asarray(jnp.sum(p[:, :24], axis=-1)))
    stat = jnp.broadcast_to(p[:, :1], (8, 128))
    assert fa._across(stat, 128) is stat
    assert fa._across(stat, 512).shape == (8, 512)
    np.testing.assert_array_equal(np.asarray(fa._across(stat, 512)),
                                  np.asarray(jnp.broadcast_to(p[:, :1],
                                                              (8, 512))))
    assert fa._across(stat, 24).shape == (8, 1)


# ---------------------------------------------------------------------------
# a stated mask (PR 46): runs of key columns, each under a rule of its own —
# the pair list and the kernels' tile made from the ONE statement — against
# scores written out under a mask written out
# ---------------------------------------------------------------------------


def _pooled(k, v, phi, mu, chunk):
    """Chunk summaries, written out: softmax over the chunk of k·φ/√d."""
    B, L, H, d = k.shape
    kc, vc = (x.reshape(B, L // chunk, chunk, H, d) for x in (k, v))
    w = jax.nn.softmax(jnp.einsum("bmchd,hd->bmch", kc, phi) / np.sqrt(d),
                       axis=2)
    return (jnp.einsum("bmch,bmchd->bmhd", w, kc) + mu,
            jnp.einsum("bmch,bmchd->bmhd", w, vc))


def _eva_plain(q, k, v, phi, mu, window, chunk):
    """EVA attention as one dense softmax over [every summary ‖ every key]
    under the mask in words: summary m iff its chunk lies in an earlier
    window than the row's, key j iff in the row's window and j <= i."""
    L, d = q.shape[1], q.shape[-1]
    ks, vs = _pooled(k, v, phi, mu, chunk)
    i = np.arange(L)[:, None]
    seen = np.concatenate(
        [(np.arange(L // chunk)[None] * chunk) // window < i // window,
         (np.arange(L)[None] // window == i // window)
         & (np.arange(L)[None] <= i)], axis=1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q,
                   jnp.concatenate([ks, k], axis=1)) / np.sqrt(d)
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.concatenate([vs, v], axis=1))


_EVA_CASES = {}
_EVA_NAMES = ("out", "dq", "dk", "dv", "dphi", "dmu")


def _eva_case(block):
    """{name: (kernels', plain)} on 64 positions, windows of 16, chunks of
    4 — the first window has no summary, a window's last chunk ends on its
    edge — in ``block``-blocks (8: two q blocks a window and a padded,
    half-empty second summary block; 16: one), NaN-filled memory."""
    from paddle_tpu.ops import eva

    if block not in _EVA_CASES:
        rng = np.random.default_rng(51)
        q, k, v, w = (jnp.asarray(rng.normal(size=(1, 64, 2, 8)).astype(
            np.float32)) for _ in range(4))
        phi, mu = (jnp.asarray(rng.normal(size=(2, 8)).astype(np.float32))
                   for _ in range(2))
        kw = dict(interpret=NAN_FILLED, precision="highest")

        def kernels(q, k, v, phi, mu):
            if block == 16:         # ``eva_attention``'s own: a window's
                return eva.eva_attention(q, k, v, phi, mu, 16, 4, **kw)
            keys, values = eva._with_summaries(k, v, phi, mu, 16, 4,
                                               8 ** -0.5)
            return flash_attention(q, keys, values,
                                   mask=eva.eva_mask(64, 16, 4),
                                   block_q=block, block_k=block, **kw)

        plain = lambda *a: _eva_plain(*a, 16, 4)
        got, want = [
            (f(q, k, v, phi, mu),) + jax.grad(
                lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4))(
                    q, k, v, phi, mu) for f in (kernels, plain)]
        _EVA_CASES[block] = dict(zip(_EVA_NAMES, zip(got, want)))
    return _EVA_CASES[block]


@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("what", _EVA_NAMES)
def test_eva_mask_matches_the_plain_softmax(block, what):
    """The forward and the three cotangents — and what flows on through
    the pooling to φ and μ — under the two-run mask, one running softmax
    over summaries and keys."""
    got, want = _eva_case(block)[what]
    assert np.isfinite(np.asarray(got)).all()       # every block written
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=3e-6)


def test_eva_pair_list_walks_what_the_mask_leaves():
    """8192 positions in 512-blocks, windows of 2048, chunks of 16: one
    block of 384 summaries and 16 of keys. A window's own lower triangle
    (4 x 10 pairs) and, for the q blocks of windows 1..3, the summary
    block (12): 52 of the 16 x 17 rectangle, no q block of the first
    window in the summaries' run."""
    from paddle_tpu.ops import eva

    mask = eva.eva_mask(8192, 2048, 16)
    assert mask == fa.Mask((fa.Keys(count=384, stride=16, earlier=True),
                            fa.Keys(count=8192)), aligned=2048)
    keep = fa._causal_pairs(16, 17, 512, 512, mask, (0, 0))
    assert int(keep.sum()) == 52 and keep.size == 272
    assert keep[:, 0].tolist() == [False] * 4 + [True] * 12
    local = keep[:, 1:]
    assert int(local.sum()) == 40
    for i in range(16):
        assert local[i].nonzero()[0].tolist() == list(
            range(i - i % 4, i + 1))
    qi, kj, ends = (np.asarray(t) for t in fa._pair_tables(keep, True))
    assert kj[:12].tolist() == [0] * 12 and qi[:12].tolist() == list(
        range(4, 16))                       # the summaries' run, k-major
    # the body's own mask on the same blocks: the one statement
    for i, j in ((0, 0), (3, 0), (4, 0), (5, 3), (5, 5), (5, 6), (8, 5)):
        assert bool(fa._reached(mask, (0, 0, 0), i, j, 512, 512,
                                np.where).some) == bool(keep[i, j])
    assert eva.eva_mask(2048, 2048, 16) == fa.Mask((fa.Keys(count=2048),),
                                                   aligned=2048)


@pytest.mark.parametrize("mask,reference", [
    (fa.Mask(aligned=16), "aligned"),
    (fa.Mask(window=5), "band"), (fa.CAUSAL, "causal")])
def test_a_stated_mask_of_one_run_matches_the_einsum(mask, reference):
    """``mask=`` with the keys whole: aligned windows alone (a
    block-diagonal causal mask), and the two masks ``causal`` and
    ``window`` state — which trace to the program those keywords give."""
    rng = np.random.default_rng(52)
    q, k, v = _qkv(rng, B=1, L=64, H=2, D=8)
    kw = dict(block_q=16, block_k=16, interpret=NAN_FILLED,
              precision="highest")
    stated = lambda q, k, v: flash_attention(q, k, v, mask=mask, **kw)
    if reference == "aligned":
        i = np.arange(64)
        seen = (i[None] // 16 == i[:, None] // 16) & (i[None] <= i[:, None])

        def want(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)
        assert int(fa._causal_pairs(4, 4, 16, 16, mask, (0, 0)).sum()) == 4
    else:
        keyword = dict(causal=True, window=5 if reference == "band" else None)
        want = lambda q, k, v: flash_attention(q, k, v, **keyword, **kw)
        assert str(jax.make_jaxpr(stated)(q, k, v)) == str(
            jax.make_jaxpr(want)(q, k, v))
    loss = lambda f: lambda *a: jnp.sum(f(*a) ** 2)
    np.testing.assert_allclose(np.asarray(stated(q, k, v)),
                               np.asarray(want(q, k, v)), atol=3e-6)
    for a, b in zip(jax.grad(loss(stated), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(want), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("case,kw,match", [
    ("traced offsets", dict(mask=fa.CAUSAL, traced=True), "Python ints"),
    ("causal beside a mask", dict(mask=fa.CAUSAL, causal=True),
     "whole statement"),
    ("a window beside a mask", dict(mask=fa.CAUSAL, window=4),
     "whole statement"),
    ("runs that do not cover the keys",
     dict(mask=fa.Mask((fa.Keys(count=8, stride=4, earlier=True),
                        fa.Keys(count=32)), aligned=16)), "cover the keys"),
    ("runs at an offset",
     dict(mask=fa.Mask((fa.Keys(count=16), fa.Keys(count=48)), aligned=16),
          q_offset=16), "count positions from 0"),
    ("a q block across two windows", dict(mask=fa.Mask(aligned=24)),
     "share one window"),
    ("a stride of nought",
     dict(mask=fa.Mask((fa.Keys(count=64, stride=0),))), "at least 1"),
    ("earlier without aligned",
     dict(mask=fa.Mask((fa.Keys(earlier=True),))), "needs `aligned`"),
    ("a tuple of runs", dict(mask=(fa.Keys(),)), "flash_attention.Mask"),
    ("no Keys", dict(mask=fa.Mask((True,))), "flash_attention.Keys")])
def test_what_a_stated_mask_does_not_accept_is_refused(case, kw, match):
    from paddle_tpu.core.enforce import EnforceNotMet

    rng = np.random.default_rng(53)
    q, k, v = _qkv(rng, B=1, L=64, H=1, D=8)
    kw = dict(kw, block_q=16, block_k=16, interpret=True)
    with pytest.raises(EnforceNotMet, match=match):
        if kw.pop("traced", False):
            jax.jit(lambda o: flash_attention(q, k, v, q_offset=o, **kw))(
                jnp.int32(0))
        else:
            flash_attention(q, k, v, **kw)


#: sha256[:16] of ``str(jax.make_jaxpr(...))`` of value_and_grad of each
#: call — the kernels' bodies, grids and tables with it — taken from the
#: PARENT of PR 46 (``git archive``) and from this tree, and equal: the
#: stated mask changed no program that existed
_JAXPR_OF_THE_PARENT = {
    "causal": (dict(causal=True), (2, 4096, 16, 128), 128,
               "ea21123abd593017"),
    "causal_small_blocks": (dict(causal=True, block_q=16, block_k=16),
                            (1, 64, 2, 8), 8, "4773fcc73deb4fbd"),
    "window": (dict(causal=True, window=4096), (1, 16384, 28, 128), 128,
               "2ece8861c33d8eae"),
    "window_small": (dict(causal=True, window=17, block_q=16, block_k=16),
                     (1, 64, 2, 8), 8, "9fbe2f655a179b4d"),
    "window_offsets": (dict(causal=True, window=24, q_offset=32, k_offset=16,
                            block_q=16, block_k=16), (1, 32, 2, 8), 8,
                       "bed7eb8e5766d8e8"),
    "bidirectional": (dict(causal=False), (32, 512, 12, 64), 64,
                      "72f5f71327e81263"),
    "latent": (dict(causal=True), (1, 4096, 32, 192), 128,
               "53b13e271d6660eb"),
    "rectangle_for_an_unnamed_block": (
        dict(causal=True, q_offset=0, k_offset=32, block_q=16, block_k=16),
        (1, 64, 2, 8), 8, "f11a88806ffe5ccc"),
    "highest": (dict(causal=True, precision="highest"), (1, 1024, 4, 64), 64,
                "3533d1af433cbfeb"),
}


@pytest.mark.parametrize("case", sorted(_JAXPR_OF_THE_PARENT))
def test_existing_calls_trace_to_the_program_they_had(case):
    import hashlib

    kw, shape, dv, want = _JAXPR_OF_THE_PARENT[case]
    q, v = jnp.zeros(shape, jnp.float32), jnp.zeros(shape[:3] + (dv,),
                                                    jnp.float32)
    fwd_bwd = lambda q, k, v: jax.value_and_grad(
        lambda q, k, v: (flash_attention(q, k, v, interpret=False, **kw)
                         ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    text = str(jax.make_jaxpr(fwd_bwd)(q, q, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


def test_the_cp_rings_call_traces_to_the_program_it_had():
    """Traced offsets and an lse cotangent (``parallel/ring_attention``'s
    call): the rectangle, as in the parent of PR 46."""
    import hashlib

    z = jnp.zeros((1, 256, 2, 16), jnp.float32)

    def g(q, k, v, o):
        out, lse = flash_attention_with_lse(q, k, v, causal=True, q_offset=o,
                                            k_offset=0, interpret=False)
        return (out ** 2).sum() + lse.sum()

    text = str(jax.make_jaxpr(jax.grad(g, argnums=(0, 1, 2)))(
        z, z, z, jnp.int32(128)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "a1b6e13c0b9151f0"

"""Pallas flash attention: fwd/bwd parity vs the einsum reference
(interpret mode on CPU; the same kernels compile on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("precision,bits", [("default", 16), ("highest", 32)])
def test_operand_span_is_recorded_once_a_compile(precision, bits):
    """``pt.flash.operands``: one host span a trace with the width of the
    arrays the forward kernel was handed, none on the step path."""
    from paddle_tpu.core import profiler

    rng = np.random.default_rng(9)
    q, k, v = _qkv(rng, B=1, L=32, H=2, D=8)
    step = jax.jit(lambda q, k, v: _out_and_grads(q, k, v, False, precision))
    spans = lambda: [s.counts for s in profiler.host_spans()
                     if s.name == "pt.flash.operands"]
    before = len(spans())
    for _ in range(3):
        jax.block_until_ready(step(q, k, v))
    assert spans()[before:] == [{"bits": bits, "head_dim": 8, "lanes": 128,
                                 "v_head_dim": 8}]

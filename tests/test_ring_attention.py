"""Context parallelism: ring attention and Ulysses must match full
(serial) attention, causal and non-causal, forward and backward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from paddle_tpu.core import mesh as mesh_mod
from paddle_tpu.ops import collectives as coll
from paddle_tpu.parallel.ring_attention import (
    local_attention,
    ring_attention,
    ulysses_attention,
)

B, L, H, D = 2, 16, 4, 8  # global seq L over cp=4 → 4 per rank


@pytest.fixture(scope="module")
def mesh():
    return mesh_mod.make_mesh({"dp": 2, "cp": 4})


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda: rng.normal(size=(B, L, H, D)).astype(np.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(mesh, qkv, causal):
    q, k, v = qkv
    ref = local_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)

    out = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis="cp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp")),
        out_specs=P(None, "cp"),
    )(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(mesh, qkv, causal):
    q, k, v = qkv
    ref = local_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=causal)

    out = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis="cp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp")),
        out_specs=P(None, "cp"),
    )(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_ring_attention_backward_matches_full(mesh, qkv):
    q, k, v = qkv

    def ref_loss(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    )

    def ring_loss(q, k, v):
        out = ring_attention(q, k, v, axis="cp", causal=True)
        # the loss cotangent is replicated over cp: psum_replicated
        # pins the identity backward so per-rank cotangents stay unscaled
        return coll.psum_replicated(jnp.sum(out ** 2), "cp")

    grads = shard_map(
        jax.grad(ring_loss, argnums=(0, 1, 2)),
        mesh=mesh,
        in_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp")),
        out_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp")),
    )(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for g, rg in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg), rtol=3e-4, atol=3e-5)


@pytest.mark.slow
def test_ring_attention_long_sequence_cp8():
    """Long-context: a 2048-token causal sequence over the full 8-way cp
    axis (256 tokens/rank) still matches full attention — the scale
    regime the ring exists for, not just the toy lengths above. Also
    runs fwd+bwd so the rotation's VJP is exercised at length."""
    mesh8 = mesh_mod.make_mesh({"cp": 8})
    Bl, Ll, Hl, Dl = 1, 2048, 4, 32
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.normal(size=(Bl, Ll, Hl, Dl)), jnp.float32)
               for _ in range(3))

    # differentiate w.r.t. ALL of q, k, v — the k/v cotangents flow
    # through the ppermute rotation's transpose, the path this test
    # exists to pin at length
    ref_loss, ref_grads = jax.value_and_grad(
        lambda q, k, v: jnp.sum(local_attention(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis="cp", causal=True),
        mesh=mesh8,
        in_specs=(P(None, "cp"), P(None, "cp"), P(None, "cp")),
        out_specs=P(None, "cp"))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(ring(q, k, v) ** 2),
        argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)
    for g, rg in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   rtol=2e-3, atol=2e-3)

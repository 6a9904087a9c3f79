"""Ernie flagship model: serial vs sharded parity on the virtual 8-device
mesh (the reference validates TP/PP numerics by comparing distributed
losses against single-process runs — test_dist_base.py pattern)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.core import mesh as mesh_mod
from paddle_tpu.models.ernie import (Ernie, ErnieConfig, parallel_cross_entropy,
                                     partition_spec)

CFG = ErnieConfig(vocab_size=32, hidden_size=16, num_heads=4, ffn_size=32,
                  num_layers=2, max_seq_len=64)


def _specs(state, cfg, mesh):
    # mirror the exact pytree type (get_state returns OrderedDicts) and
    # drop axes the mesh doesn't have
    def spec(path, a):
        p = partition_spec(path[-1].key, a, cfg)
        return P(*[ax if ax in mesh.shape else None for ax in p])

    return jax.tree_util.tree_map_with_path(spec, state)


def _serial_loss(model, state, ids, labels):
    out, _ = nn.functional_call(model, state, ids, training=False)
    ce = nn.functional.cross_entropy(out, labels, reduction="none")
    return jnp.mean(ce)


def _sharded_loss(model, cfg, mesh, state, ids, labels):
    specs = _specs(state, cfg, mesh)

    def f(st, ids, labels):
        out, _ = nn.functional_call(model, st, ids, training=False)
        ce = parallel_cross_entropy(out, labels, cfg.vocab_size, cfg.mp_axis)
        local = jnp.mean(ce)
        batch_axes = tuple(a for a in ("dp", "cp") if a in mesh.shape)
        denom = int(np.prod([mesh.shape[a] for a in batch_axes]))
        return jax.lax.psum(local / denom, batch_axes)

    data_axes = [a for a in ("dp", "cp") if a in mesh.shape]
    ids_spec = P(data_axes[0] if "dp" in mesh.shape else None,
                 "cp" if "cp" in mesh.shape else None)
    return shard_map(f, mesh=mesh, in_specs=(specs, ids_spec, ids_spec),
                     out_specs=P())(state, ids, labels)


def _data(cfg, batch=4, seq=8):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(labels)


def test_serial_forward_shapes():
    pt.seed(0)
    model = Ernie(CFG)
    ids, labels = _data(CFG)
    logits = model(ids)
    assert logits.shape == (4, 8, CFG.vocab_size)
    loss = model.loss(ids, labels)
    assert np.isfinite(float(loss))


def test_tp_matches_serial():
    pt.seed(0)
    model = Ernie(CFG)
    state = nn.get_state(model)
    ids, labels = _data(CFG)
    serial = _serial_loss(model, state, ids, labels)
    mesh = mesh_mod.make_mesh({"dp": 2, "mp": 4})
    sharded = _sharded_loss(model, CFG, mesh, state, ids, labels)
    np.testing.assert_allclose(float(sharded), float(serial), rtol=1e-4)


def test_cp_matches_serial():
    pt.seed(1)
    model = Ernie(CFG)
    state = nn.get_state(model)
    ids, labels = _data(CFG)
    serial = _serial_loss(model, state, ids, labels)
    mesh = mesh_mod.make_mesh({"dp": 2, "cp": 4})
    sharded = _sharded_loss(model, CFG, mesh, state, ids, labels)
    np.testing.assert_allclose(float(sharded), float(serial), rtol=1e-4)


def test_causal_cp_matches_serial():
    cfg = dataclasses.replace(CFG, causal=True)
    pt.seed(2)
    model = Ernie(cfg)
    state = nn.get_state(model)
    ids, labels = _data(cfg)
    serial = _serial_loss(model, state, ids, labels)
    mesh = mesh_mod.make_mesh({"cp": 8})
    sharded = _sharded_loss(model, cfg, mesh, state, ids, labels)
    np.testing.assert_allclose(float(sharded), float(serial), rtol=1e-4)


def test_moe_ep_matches_serial():
    cfg = dataclasses.replace(CFG, num_experts=4, ep_axis="dp")
    pt.seed(3)
    model = Ernie(cfg)
    state = nn.get_state(model)
    ids, labels = _data(cfg, batch=8)
    serial = _serial_loss(model, state, ids, labels)
    mesh = mesh_mod.make_mesh({"dp": 2, "mp": 4})
    sharded = _sharded_loss(model, cfg, mesh, state, ids, labels)
    # token grid differs between serial (one dispatch over all tokens) and
    # ep (per-dp-shard dispatch): capacity truncation can drop different
    # tokens, so compare loosely
    np.testing.assert_allclose(float(sharded), float(serial), rtol=0.05)


def test_tp_grads_match_serial():
    """TP+DP gradients vs jax.grad of the serial model — written in the
    sanctioned explicit-reduction pattern (the hybrid trainer's): the
    loss psum and the PCE reductions are identity-VJP
    (``pinned_vjp=True``), the shard_map runs ``check_vma=False``, and
    each param's grad is explicitly psum'd over every mesh axis it is
    NOT sharded on."""
    from paddle_tpu.ops import collectives as coll

    pt.seed(4)
    model = Ernie(CFG)
    state = nn.get_state(model)
    ids, labels = _data(CFG)
    gs = jax.grad(lambda st: _serial_loss(model, st, ids, labels))(state)
    mesh = mesh_mod.make_mesh({"dp": 2, "mp": 4})
    specs = _specs(state, CFG, mesh)

    def f(st, ids, labels):
        def loss(st):
            out, _ = nn.functional_call(model, st, ids, training=False)
            ce = parallel_cross_entropy(out, labels, CFG.vocab_size, "mp",
                                        pinned_vjp=True)
            return coll.psum_replicated(jnp.mean(ce) / 2, ("dp",))

        grads = jax.grad(loss)(st)
        return coll.spec_reduced_grads(grads, specs, dict(mesh.shape))

    gd = shard_map(f, mesh=mesh, in_specs=(specs, P("dp", None), P("dp", None)),
                   out_specs=specs, check_vma=False)(state, ids, labels)
    for name, g in gs["params"].items():
        np.testing.assert_allclose(np.asarray(gd["params"][name]),
                                   np.asarray(g), rtol=2e-3, atol=1e-5,
                                   err_msg=name)

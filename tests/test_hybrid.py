"""HybridParallelTrainer: dp×pp×cp×mp single-step parity vs serial and
multi-step convergence on the 8-device virtual mesh."""

import pytest
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.core import mesh as mesh_mod
from paddle_tpu.models.ernie import Ernie, ErnieConfig
from paddle_tpu.parallel.hybrid import HybridParallelTrainer

CFG = ErnieConfig(vocab_size=32, hidden_size=16, num_heads=4, ffn_size=32,
                  num_layers=2, max_seq_len=64)


def _data(cfg, batch, seq, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(labels)


def _serial_loss_from_trainer(trainer, cfg, ids, labels):
    """Assemble a serial Ernie from the trainer's stacked params and
    compute the plain loss (parity oracle)."""
    params = jax.device_get(trainer.params)
    serial = Ernie(cfg)
    pp = serial_blocks = cfg.num_layers
    stages = params["stages"]
    n_stages = next(iter(stages["params"].values())).shape[0]
    bps = cfg.num_layers // n_stages
    state = {"params": {}, "buffers": {}}
    for group in ("params", "buffers"):
        for name, arr in stages[group].items():
            # stage-local name "blocks.b.rest" → serial "blocks.{s*bps+b}.rest"
            parts = name.split(".")
            for s in range(n_stages):
                i = s * bps + int(parts[1])
                state[group][".".join(["blocks", str(i)] + parts[2:])] = arr[s]
        for name, arr in params["aux"]["embed"][group].items():
            state[group]["embed." + name] = arr
        for name, arr in params["aux"]["head"][group].items():
            state[group]["head." + name] = arr
    out, _ = nn.functional_call(serial, state, ids, training=False)
    ce = nn.functional.cross_entropy(out, labels, reduction="none")
    return float(jnp.mean(ce))


def test_hybrid_first_loss_matches_serial():
    pt.seed(0)
    mesh = mesh_mod.make_mesh({"dp": 1, "pp": 2, "cp": 2, "mp": 2})
    trainer = HybridParallelTrainer(CFG, mesh, optimizer.SGD(learning_rate=0.1),
                                    num_micro=2)
    ids, labels = _data(CFG, batch=4, seq=8)
    serial = _serial_loss_from_trainer(trainer, trainer.cfg, ids, labels)
    loss = float(trainer.train_step(ids, labels))
    np.testing.assert_allclose(loss, serial, rtol=1e-4)


def test_hybrid_loss_decreases():
    pt.seed(1)
    mesh = mesh_mod.make_mesh({"dp": 2, "pp": 2, "cp": 1, "mp": 2})
    trainer = HybridParallelTrainer(CFG, mesh, optimizer.Adam(learning_rate=1e-2),
                                    num_micro=2)
    ids, labels = _data(CFG, batch=8, seq=8)
    first = float(trainer.train_step(ids, labels))
    for _ in range(10):
        last = float(trainer.train_step(ids, labels))
    assert last < first, (first, last)


def test_hybrid_moe_runs():
    cfg = dataclasses.replace(CFG, num_experts=4)
    pt.seed(2)
    mesh = mesh_mod.make_mesh({"dp": 2, "pp": 2, "cp": 1, "mp": 2})
    trainer = HybridParallelTrainer(cfg, mesh, optimizer.SGD(learning_rate=0.1),
                                    num_micro=2)
    ids, labels = _data(cfg, batch=8, seq=8)
    first = float(trainer.train_step(ids, labels))
    assert np.isfinite(first)
    for _ in range(5):
        last = float(trainer.train_step(ids, labels))
    assert last < first, (first, last)


@pytest.mark.slow
def test_hybrid_realistic_width_converges():
    """Hybrid step at non-toy width (hidden 128, 4 layers, vocab 512,
    seq 128 over cp=2) on the full 8-device dp×pp×cp×mp mesh: several
    steps must reduce loss — exercises sharding-constraint edges the
    tiny shapes cannot (head dims, ffn splits, vocab partitions all
    > 1 element per shard)."""
    cfg = ErnieConfig(vocab_size=512, hidden_size=128, num_heads=4,
                      ffn_size=256, num_layers=4, max_seq_len=128)
    mesh = mesh_mod.make_mesh({"dp": 1, "pp": 2, "cp": 2, "mp": 2})
    tr = HybridParallelTrainer(cfg, mesh, optimizer.Adam(3e-3), num_micro=2)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, size=(4, 128)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    losses = [float(tr.train_step(ids, labels)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.1, losses


def test_hybrid_save_load_resume(tmp_path):
    """Checkpoint mid-training and resume in a fresh trainer: the next
    steps follow the same trajectory (params + opt state + rng + step
    counter all restored; global-shape params make the snapshot mesh-
    layout-independent)."""
    mesh = mesh_mod.make_mesh({"dp": 2, "pp": 2, "cp": 1, "mp": 2})
    rng = np.random.default_rng(0)
    ids = rng.integers(0, CFG.vocab_size, size=(8, 8)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    pt.seed(0)
    a = HybridParallelTrainer(CFG, mesh, optimizer.Adam(1e-3), num_micro=2)
    for _ in range(3):
        a.train_step(ids, labels)
    a.save(str(tmp_path / "snap"))
    la = [float(a.train_step(ids, labels)) for _ in range(3)]

    pt.seed(0)
    b = HybridParallelTrainer(CFG, mesh, optimizer.Adam(1e-3), num_micro=2)
    b.load(str(tmp_path / "snap"))
    assert b.global_step == 3
    lb = [float(b.train_step(ids, labels)) for _ in range(3)]
    np.testing.assert_allclose(lb, la, rtol=1e-5)


def test_hybrid_sharding_axis_shards_opt_state():
    """dp×pp×cp×mp×sh: optimizer slots are device-sharded over the "sh"
    axis (ZeRO/sharding_optimizer role) while params stay global; one
    step runs and every sharded slot leaf holds 1/sh of the rows."""
    pt.seed(0)
    mesh = mesh_mod.make_mesh({"dp": 1, "pp": 2, "cp": 1, "mp": 2, "sh": 2})
    tr = HybridParallelTrainer(CFG, mesh, optimizer.Adam(1e-2), num_micro=2)
    def axes_of(spec):
        out = []
        for e in tuple(spec):
            out.extend(e if isinstance(e, tuple) else [e])
        return out

    sharded = [
        leaf for leaf in jax.tree_util.tree_leaves(tr.opt_state["slots"])
        if "sh" in axes_of(leaf.sharding.spec)
    ]
    assert sharded, "no slot leaf is sharded over sh"
    for leaf in sharded:
        local = leaf.addressable_shards[0].data.size
        assert local * 2 <= leaf.size, (local, leaf.size)
    ids, labels = _data(CFG, batch=4, seq=8)
    loss = tr.train_step(ids, labels)
    assert np.isfinite(float(loss))
    # the sh constraint survives the compiled update (donated buffers)
    post = [
        leaf for leaf in jax.tree_util.tree_leaves(tr.opt_state["slots"])
        if "sh" in axes_of(leaf.sharding.spec)
    ]
    assert len(post) == len(sharded), (len(post), len(sharded))


@pytest.mark.slow
def test_hybrid_sharding_matches_unsharded_and_restores(tmp_path):
    """The sh axis is an inner data-parallel group: dp1×sh2 follows the
    same trajectory as dp2 unsharded (sharding changes memory layout,
    not math — sharding_optimizer parity), and a snapshot taken from the
    sharded trainer restores into an UNSHARDED trainer (different shard
    factorization) and continues identically."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, CFG.vocab_size, size=(8, 8)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    pt.seed(0)
    mesh_sh = mesh_mod.make_mesh({"dp": 1, "pp": 2, "cp": 1, "mp": 2, "sh": 2})
    a = HybridParallelTrainer(CFG, mesh_sh, optimizer.Adam(1e-2), num_micro=2)
    pt.seed(0)
    mesh_dp = mesh_mod.make_mesh({"dp": 2, "pp": 2, "cp": 1, "mp": 2})
    b = HybridParallelTrainer(CFG, mesh_dp, optimizer.Adam(1e-2), num_micro=2)

    for i in range(3):
        la, lb = a.train_step(ids, labels), b.train_step(ids, labels)
        np.testing.assert_allclose(float(la), float(lb), rtol=2e-5,
                                   err_msg=f"step {i}")

    a.save(str(tmp_path / "snap"))
    la = [float(a.train_step(ids, labels)) for _ in range(2)]
    pt.seed(1)  # different init — must be fully overwritten by load
    c = HybridParallelTrainer(CFG, mesh_dp, optimizer.Adam(1e-2), num_micro=2)
    c.load(str(tmp_path / "snap"))
    assert c.global_step == a.global_step - 2
    lc = [float(c.train_step(ids, labels)) for _ in range(2)]
    np.testing.assert_allclose(lc, la, rtol=2e-5)


@pytest.mark.slow
def test_hybrid_grads_match_serial():
    """The serial-gradient oracle for the hybrid step's explicit
    reductions (hybrid loss, pipe masked psum, parallel_cross_entropy):
    un-pinned psums once produced grads that were ×mp on aux params and
    ZERO on the head while every loss-only test passed. One SGD(lr=1)
    step must reproduce jax.grad of the equivalent serial model to fp32
    roundoff on every parameter."""
    pt.seed(0)
    mesh = mesh_mod.make_mesh({"dp": 2, "pp": 2, "cp": 1, "mp": 2})
    tr = HybridParallelTrainer(CFG, mesh, optimizer.SGD(1.0), num_micro=2)
    ids, labels = _data(CFG, batch=8, seq=8)

    params = jax.device_get(tr.params)
    serial = Ernie(CFG)
    n_stages = next(iter(params["stages"]["params"].values())).shape[0]
    bps = CFG.num_layers // n_stages
    state = {"params": {}, "buffers": {}}
    for group in ("params", "buffers"):
        for name, arr in params["stages"][group].items():
            parts = name.split(".")
            for s in range(n_stages):
                i = s * bps + int(parts[1])
                state[group][".".join(["blocks", str(i)] + parts[2:])] = arr[s]
        for name, arr in params["aux"]["embed"][group].items():
            state[group]["embed." + name] = arr
        for name, arr in params["aux"]["head"][group].items():
            state[group]["head." + name] = arr

    def loss_fn(p):
        out, _ = nn.functional_call(
            serial, {"params": p, "buffers": state["buffers"]}, ids,
            training=False)
        ce = nn.functional.cross_entropy(out, labels, reduction="none")
        return jnp.mean(ce)

    gs = jax.grad(loss_fn)(state["params"])
    tr.train_step(ids, labels)          # SGD lr=1: delta == gradient
    p1 = jax.device_get(tr.params)

    for name, arr in params["stages"]["params"].items():
        g = np.asarray(arr) - np.asarray(p1["stages"]["params"][name])
        rest = name.split(".", 2)[2]
        b = int(name.split(".")[1])
        for s in range(n_stages):
            np.testing.assert_allclose(
                g[s], np.asarray(gs[f"blocks.{s * bps + b}.{rest}"]),
                atol=5e-6, err_msg=f"stage{s}.{name}")
    for an in ("embed", "head"):
        for pn, arr in params["aux"][an]["params"].items():
            g = np.asarray(arr) - np.asarray(p1["aux"][an]["params"][pn])
            np.testing.assert_allclose(g, np.asarray(gs[f"{an}.{pn}"]),
                                       atol=5e-6, err_msg=f"{an}.{pn}")

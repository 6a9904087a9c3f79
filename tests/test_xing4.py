"""Xing4.0-29B-A4B on the dense path: ``models.Joyai`` under ``hc_mult`` 4,
YaRN and no prediction module, through ``executor.make_train_step`` against
the plain reference that sits beside the benchmark's configuration; the
Sinkhorn normalisation alone; the residual path's four kernels against the
plain definitions, their count of stream-widths in a sublayer's jaxpr and
their scopes in the compiled step; the hyper-connections paper's
equivalence with the one-stream block; YaRN's frequencies and ``m²`` by
hand; the eight shares adding up to the uncut layer; routes and the bias update leaving a
recomputed block once; the parameter counts; the benchmark's FLOP and byte
counts by hand; the planted faults of the cell's ``correct``; the cell's
rehearsal end to end."""

import contextlib
import importlib.util
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.core import profiler
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.executor import Trainer, make_train_step
from paddle_tpu.models import Joyai, JoyaiConfig
from paddle_tpu.models import transformer
from paddle_tpu.models.transformer import (HyperConnected, next_token_loss,
                                           rotary_pairs, yarn_frequencies,
                                           yarn_mscale)
from paddle_tpu.ops.hyper_connection import (hc_collect, hc_gates,
                                             hc_mappings, hc_post, hc_pre,
                                             hc_res_err, hc_scatter,
                                             sinkhorn)
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LR = 2.0 ** 16
_BIAS = "e_score_correction_bias"


def _load(name, *parts):
    path = os.path.join(ROOT, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
REF = _load("_xing4_reference", "configs", "xing4.0-29b-a4b.reference.py")
FLOPS = _load("_flops_mhc", "harness", "flops_mhc.py")
BYTES = _load("_bytes_mhc", "harness", "bytes_mhc.py")
CONTROL = _load("_mhc_fault_control", "tests", "mhc_fault_control.py")

#: the published ``rope_scaling``
YARN = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096}
#: YaRN from 8 positions: the ramp lies inside the small model's 4 pairs
SMALL_YARN = dict(YARN, original_max_position_embeddings=8)
#: 1 dense + 2 expert layers over 4 streams; q.k 24 / v 16; no module
SMALL = dict(vocab_size=97, hidden_size=32, num_heads=4, num_layers=3,
             dense_size=48, q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8,
             v_dim=16, num_experts=8, experts_per_token=2, expert_size=16,
             routed_scale=2.0, max_seq_len=16, init_std=0.05, num_mtp=0,
             hc_mult=4, rope_scaling=SMALL_YARN, rope_theta=10000.0)
#: the published widths
WIDTHS = dict(hidden_size=3584, num_heads=32, dense_size=9216, q_rank=768,
              kv_rank=512, nope_dim=128, rope_dim=64, v_dim=128,
              num_experts=64, experts_per_token=4, expert_size=1024,
              num_shared=1, routed_scale=2.0, num_mtp=0, hc_mult=4,
              rope_scaling=YARN, rope_theta=10000.0)


def _ref_cfg(cfg: JoyaiConfig):
    """The model's sizes under the configuration file's keys."""
    return {"num_hidden_layers": cfg.num_layers,
            "first_k_dense_replace": cfg.first_dense,
            "num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.nope_dim, "qk_rope_head_dim": cfg.rope_dim,
            "v_head_dim": cfg.v_dim, "kv_lora_rank": cfg.kv_rank,
            "num_experts_per_tok": cfg.experts_per_token,
            "router_width": cfg.num_experts, "held_first": cfg.held[0],
            "n_routed_experts": cfg.held[1],
            "routed_scaling_factor": cfg.routed_scale,
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "num_nextn_predict_layers": cfg.num_mtp,
            "bias_update_rate": cfg.bias_update_rate,
            "hc_mult": cfg.hc_mult,
            "hc_sinkhorn_iters": cfg.hc_sinkhorn_iters,
            "hc_eps": cfg.hc_eps, "mhc_h_res_clamp_min": cfg.hc_clamp[0],
            "mhc_h_res_clamp_max": cfg.hc_clamp[1],
            "rope_scaling": cfg.rope_scaling}


def _batch(cfg: JoyaiConfig, batch: int, seed: int):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len + 1),
                        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def _seeded_state(model, seed):
    """The model's state with biases as a trained model's (without them
    "the bias moves the choice" is not exercised) and mappings off their
    initial values (gates of 0.3 where the start is 0.01, every ``b``
    moved: a gradient to ``phi`` that a start of 0.01 would scale away)."""
    rng = np.random.default_rng(seed)
    state = jax.tree_util.tree_map(jnp.array, nn.get_state(model))
    for name, b in state["buffers"].items():
        if name.endswith(_BIAS):
            state["buffers"][name] = jnp.asarray(
                rng.normal(size=b.shape) * 0.02, jnp.float32)
    for name, p in state["params"].items():
        if name.endswith(".alpha"):
            state["params"][name] = jnp.asarray(
                rng.uniform(0.2, 0.4, p.shape), jnp.float32)
        elif ".hc_" in name and name.endswith(".b"):
            state["params"][name] = p + jnp.asarray(
                rng.normal(size=p.shape) * 0.3, jnp.float32)
    return state


def _sgd_step(model, ids, labels, amp=False, seed=11):
    """The TRAIN STEP's loss, gradients (SGD: (before - after) / lr, lr a
    large power of two) and buffers after it."""
    state = _seeded_state(model, seed)
    opt = optimizer.SGD(learning_rate=LR)
    step = make_train_step(model, opt, next_token_loss, donate=False, amp=amp)
    new_state, _, loss = step(state, opt.init(state["params"]),
                              jax.random.key(0), (jnp.asarray(ids),),
                              (jnp.asarray(labels),))
    grads = {k: (np.asarray(state["params"][k]) - np.asarray(v)) / LR
             for k, v in new_state["params"].items()}
    return float(loss), grads, new_state["buffers"], state


def _got(loss, grads, buffers, logits=None):
    got = {"loss": loss, "grads": grads,
           "hc_res_err": float(buffers["hc_res_err"]),
           "bias_after": {k: np.asarray(v) for k, v in buffers.items()
                          if k.endswith(_BIAS)}}
    if logits is not None:
        got["logits"] = logits
    return got


@pytest.mark.parametrize("recompute", ["none", "blocks"])
def test_train_step_matches_reference(recompute):
    """Loss, logits, EVERY gradient leaf (``phi``, ``b`` and ``alpha`` of
    all six sublayers among them), the biases after the step and the
    ``H_res`` error, by the train step, against the plain reference — at
    the cell's float32 limits (1e-4 of a leaf's largest entry)."""
    pt.seed(3)
    cfg = JoyaiConfig(**SMALL, held=(2, 4), recompute=recompute)
    model = Joyai(cfg)
    ids, labels = _batch(cfg, 2, 0)
    with jax.default_matmul_precision("highest"):
        loss, grads, buffers, state = _sgd_step(model, ids, labels)
        logits, _ = nn.functional_call(model, state, jnp.asarray(ids),
                                       training=True)
    ref = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg),
                             buffers=state["buffers"])
    assert set(grads) == set(ref["grads"]) and len(grads) == 65
    assert sum(".hc_" in k for k in grads) == 3 * 2 * 3
    out = REF.compare(_got(loss, grads, buffers, logits), ref, "f32")
    assert out["ok"], out
    assert out["bias"]["experts_compared"] > 0
    # every leaf carries a gradient the comparison can see
    assert all(float(jnp.max(jnp.abs(g))) > 1e-7
               for g in ref["grads"].values())
    assert 0.0 < ref["hc_res_err"] < 1e-5


def test_recomputed_step_is_the_plain_step_and_stores_once():
    """``recompute: "blocks"``: the loss, every parameter after the step,
    the routing record, the moved biases and the ``H_res`` error equal the
    plain step's — the routes and the bias leave each rebuilt block as
    outputs, once — and the step traces without a leaked tracer."""
    ids, labels = None, None
    outs = {}
    for recompute in ("none", "blocks"):
        pt.seed(5)
        cfg = JoyaiConfig(**SMALL, held=(0, 8), recompute=recompute)
        model = Joyai(cfg)
        if ids is None:
            ids, labels = _batch(cfg, 2, 1)
        loss, grads, buffers, _ = _sgd_step(model, ids, labels)
        outs[recompute] = (loss, grads, jax.device_get(dict(buffers)))
    (l0, g0, b0), (l1, g1, b1) = outs["none"], outs["blocks"]
    assert l0 == l1
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=0, atol=2e-7,
                                   err_msg=k)
    assert set(b0) == set(b1)
    for k in b0:
        np.testing.assert_array_equal(b1[k], b0[k], err_msg=k)
    # the bias moved by the rate, from the step's own counts, ONCE
    moved = [k for k in b0 if k.endswith(_BIAS)]
    assert len(moved) == 2
    counts = b1["expert_counts"]
    assert counts.shape == (2, 8) and (counts.sum(axis=1) == 2 * 16 * 2).all()


def test_trainer_trains_and_updates_the_bias():
    """Through ``executor.Trainer`` on the normal path, blocks recomputed,
    under ``amp``: the loss falls and the biases and counters move."""
    pt.seed(0)
    cfg = JoyaiConfig(**SMALL, held=(2, 4), recompute="blocks")
    model = Joyai(cfg)
    trainer = Trainer(model, optimizer.AdamW(3e-3, weight_decay=0.1),
                      next_token_loss, amp=True)
    ids, labels = _batch(cfg, 4, 2)
    losses = [float(trainer.train_step(ids, labels)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.05 and np.isfinite(losses).all()
    buffers = trainer.state["buffers"]
    assert float(jnp.max(jnp.abs(
        buffers[f"blocks.1.moe.{_BIAS}"]))) > 0.0
    assert 0.0 < float(buffers["hc_res_err"]) < 1e-4
    assert int(buffers["tokens_dropped"]) == 0


# -- the Sinkhorn normalisation alone ---------------------------------------


def test_sinkhorn_is_doubly_stochastic_after_twenty_steps():
    """From a spread ``H~`` (entries over a range of 3: ratios of 20 within
    a matrix; over a range of 6 twenty steps leave columns 4e-4 off, which
    is the iteration's pace, not a fault), rows and columns sum to 1
    within 1e-5 after 20 steps; ONE step differs from twenty by far more
    than the model test's tolerance; the system's and the reference's
    agree."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(-1.5, 1.5, (64, 4, 4)), jnp.float32)
    m = np.asarray(sinkhorn(a, 20, 1e-6), np.float64)
    assert np.abs(m.sum(-1) - 1).max() < 1e-5
    assert np.abs(m.sum(-2) - 1).max() < 1e-5
    assert (m > 0).all()
    assert float(hc_res_err(jnp.asarray(m, jnp.float32))) < 1e-5
    one = np.asarray(sinkhorn(a, 1, 1e-6), np.float64)
    assert np.abs(one.sum(-2) - 1).max() > 0.1     # columns not yet
    assert np.abs(one - m).max() > 0.05 > 1e-4
    np.testing.assert_allclose(np.asarray(REF.sinkhorn(a, 20, 1e-6)), m,
                               rtol=0, atol=1e-6)


def test_the_initial_bias_tells_one_step_from_twenty():
    """``hc_res_bias_init``: H_res at step 0 is neither uniform, nor the
    identity, nor symmetric, and one Sinkhorn step is 0.06 from twenty."""
    b = jnp.asarray(transformer.hc_res_bias_init(4), jnp.float32)
    m = np.asarray(sinkhorn(b, 20, 1e-6))
    np.testing.assert_allclose(np.diag(m), 0.594, atol=1e-3)
    np.testing.assert_allclose(m[0, 1:], [0.170, 0.133, 0.103], atol=1e-3)
    assert np.abs(m - m.T).max() > 0.06
    assert np.abs(np.asarray(sinkhorn(b, 1, 1e-6)) - m).max() > 0.05
    assert np.abs(m.sum(0) - 1).max() < 2e-6


def test_the_clamp_bites_at_thirty_and_not_before():
    """``H~_res`` is clipped to +-30 before the exp: 29 passes through,
    31 reads as 30, and an entry of 200 (``exp`` overflows float32 at 89)
    stays finite."""
    n, c = 4, 8
    x = jnp.ones((1, n, c), jnp.float32)
    phi = jnp.zeros((n * c, 2 * n + n * n), jnp.float32)
    alpha = jnp.full((3,), 0.01, jnp.float32)

    def h_res(entry, clamp=(-30.0, 30.0)):
        b = jnp.zeros((2 * n + n * n,)).at[2 * n].set(entry)
        return np.asarray(hc_mappings(x, phi, b, alpha, 20, 1e-6, clamp,
                                      1e-6)[2])

    assert np.isfinite(h_res(200.0)).all()
    np.testing.assert_array_equal(h_res(31.0), h_res(30.0))
    np.testing.assert_array_equal(h_res(200.0), h_res(30.0))
    assert np.abs(h_res(29.0) - h_res(30.0)).max() > 0
    np.testing.assert_array_equal(h_res(-45.0), h_res(-30.0))
    assert np.abs(h_res(-29.0) - h_res(-30.0)).max() > 0
    # and the bound is the configuration's, not a constant
    assert np.abs(h_res(31.0, (-40.0, 40.0)) - h_res(30.0)).max() > 0


def test_mappings_match_the_reference_and_stay_float32_under_amp():
    """``hc_mappings`` against the reference's ``mappings`` on bf16
    streams; under ``amp.auto_cast`` the projection is still a float32
    matmul (the planted bf16 variant differs by far more)."""
    from paddle_tpu import amp

    rng = np.random.default_rng(1)
    n, c, T = 4, 32, 24
    x = jnp.asarray(rng.normal(size=(1, T, n, c)), jnp.float32)
    p = {"s.phi": jnp.asarray(rng.normal(size=(n * c, 24)) * 0.2, jnp.float32),
         "s.b": jnp.asarray(rng.normal(size=(24,)), jnp.float32),
         "s.alpha": jnp.asarray([0.5, 0.7, 0.9], jnp.float32)}
    cfg = {"rms_norm_eps": 1e-6, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30, "hc_sinkhorn_iters": 20,
           "hc_eps": 1e-6}
    want = REF.mappings(p, "s.", x, cfg)
    args = (p["s.phi"], p["s.b"], p["s.alpha"], 20, 1e-6, (-30.0, 30.0), 1e-6)
    with amp.auto_cast(True):
        got = hc_mappings(x, *args)
        text = str(jax.make_jaxpr(lambda x: hc_mappings(x, *args))(x))
    assert "bf16" not in text and all(g.dtype == jnp.float32 for g in got)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=2e-6)
    low = CONTROL.mappings_in_bf16(x, *args)
    assert max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(low, want)) > 1e-3


def test_one_stream_is_refused_not_special_cased():
    x = jnp.ones((2, 1, 8), jnp.float32)
    with pytest.raises(EnforceNotMet, match="at least two"):
        hc_mappings(x, jnp.zeros((8, 3)), jnp.zeros((3,)), jnp.zeros((3,)),
                    20, 1e-6, (-30.0, 30.0), 1e-6)
    with pytest.raises(EnforceNotMet, match="at least two"):
        HyperConnected(JoyaiConfig(**dict(SMALL, hc_mult=1)))


def test_identity_mappings_give_the_one_stream_block():
    """The hyper-connections paper's equivalence: ``n`` streams that start
    equal, under ``H_res = I``, ``H_pre = e_0`` and ``H_post = 1``, are
    ``n`` copies of the one-stream block ``x + f(x)`` — sublayer after
    sublayer — and their exit sum is ``n`` times its result."""
    rng = np.random.default_rng(2)
    n, c = 4, 16
    x = jnp.asarray(rng.normal(size=(3, 5, c)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(c, c)) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(c, c)) * 0.3, jnp.float32)
    fs = (lambda u: jnp.tanh(u @ w1), lambda u: jax.nn.silu(u @ w2))
    h_pre = jnp.broadcast_to(jnp.eye(n)[0], (3, 5, n))
    h_post = jnp.ones((3, 5, n))
    h_res = jnp.broadcast_to(jnp.eye(n), (3, 5, n, n))
    streams = jnp.broadcast_to(x[:, :, None, :], (3, 5, n, c))
    one = x
    for f in fs:
        streams = hc_scatter(streams, f(hc_collect(streams, h_pre)), h_post,
                             h_res)
        one = one + f(one)
    for i in range(n):
        np.testing.assert_allclose(np.asarray(streams[:, :, i]),
                                   np.asarray(one), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.sum(streams, axis=2)),
                               n * np.asarray(one), rtol=1e-6, atol=1e-5)


def test_scatter_and_collect_by_hand():
    """``u = sum_i H_pre[i] X_i``; ``X'_i = sum_j H_res[i, j] X_j +
    H_post[i] y`` — row i of H_res, not column i — in the streams' dtype."""
    x = jnp.asarray([[[1.0, 2.0], [10.0, 20.0]]])            # [1, n=2, C=2]
    h_pre = jnp.asarray([[0.5, 0.25]])
    np.testing.assert_allclose(np.asarray(hc_collect(x, h_pre)),
                               [[3.0, 6.0]])
    h_res = jnp.asarray([[[1.0, 2.0], [0.0, 1.0]]])
    y = jnp.asarray([[100.0, 200.0]])
    h_post = jnp.asarray([[1.0, 0.0]])
    np.testing.assert_allclose(
        np.asarray(hc_scatter(x, y, h_post, h_res)),
        [[[1 + 20 + 100.0, 2 + 40 + 200.0], [10.0, 20.0]]])
    low = hc_scatter(x.astype(jnp.bfloat16), y, h_post, h_res)
    assert low.dtype == jnp.bfloat16
    assert hc_collect(x.astype(jnp.bfloat16), h_pre).dtype == jnp.float32


# -- the four kernels against the plain definitions -----------------------

_MAP = (20, 1e-6, (-30.0, 30.0))
_RMS_EPS = 1e-6


def _plain_path(x, y, phi, b, alpha):
    h_pre, h_post, h_res = hc_mappings(x, phi, b, alpha, *_MAP, _RMS_EPS)
    u = hc_collect(x, h_pre)
    return hc_scatter(x, jnp.tanh(u) + y, h_post, h_res), u


def _fused_path(x, y, phi, b, alpha):
    u, z, x = hc_pre(x, phi, b, alpha, _RMS_EPS)
    _, h_post, h_res = hc_gates(z, b, alpha, x.shape[-2], *_MAP)
    return hc_post(x, jnp.tanh(u) + y, h_post, h_res), u


def _path_case(lead, n, c, dtype, seed=5):
    rng = np.random.default_rng(seed)
    m = 2 * n + n * n
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    leaves = (jnp.asarray(rng.normal(size=(*lead, n, c)), dtype),
              f32(rng.normal(size=(*lead, c))),
              f32(rng.normal(size=(n * c, m)) * 0.2),
              f32(rng.normal(size=(m,))), f32([0.5, 0.7, 0.9]))
    return leaves, f32(rng.normal(size=(*lead, n, c)))


# (tokens' shape, streams, width): small widths, and one case at four
# streams of 128 lanes whose 300 tokens are a whole tile of 256 and a
# partial one (what Phi's gradient sums over the grid has rows to leave out)
@pytest.mark.parametrize("lead,n,c", [((2, 9), 4, 32), ((3, 5), 2, 16),
                                      ((300,), 4, 128)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("recompute", ["none", "blocks"])
def test_fused_path_is_the_plain_path(lead, n, c, dtype, recompute):
    """``hc_pre`` / ``hc_gates`` / ``hc_post`` (the four kernels,
    interpreted) against ``hc_mappings`` / ``hc_collect`` / ``hc_scatter``:
    ``X'`` and ``u`` to float32 rounding (to the streams' own where they
    are bf16), and ``jax.grad`` through the stated backward against
    ``jax.grad`` through the plain definitions for every leaf — the
    streams, a result added to the sublayer's, ``phi``, ``b``, ``alpha`` —
    with the path plain and under ``jax.checkpoint`` as a recomputed block
    has it."""
    leaves, ct = _path_case(lead, n, c, jnp.dtype(dtype))
    low = dtype == "bfloat16"

    def loss(path):
        if recompute == "blocks":
            path = jax.checkpoint(path)

        def total(*leaves):
            out, u = path(*leaves)
            return jnp.sum(out.astype(jnp.float32) * ct) + 0.1 * jnp.sum(u * u)
        return total

    want = jax.jit(_plain_path)(*leaves)
    got = jax.jit(_fused_path)(*leaves)
    assert got[0].dtype == leaves[0].dtype and got[1].dtype == jnp.float32
    for g, w, tol in zip(got, want, (2.0 ** -7 if low else 3e-6, 3e-6)):
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), w, rtol=0,
                                   atol=tol * np.abs(w).max())
    every = tuple(range(len(leaves)))
    want = jax.jit(jax.grad(loss(_plain_path), argnums=every))(*leaves)
    got = jax.jit(jax.grad(loss(_fused_path), argnums=every))(*leaves)
    for name, g, w in zip(("x", "y", "phi", "b", "alpha"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        w = np.asarray(w, np.float32)
        # bf16 streams: the plain path rounds the streams' cotangent
        # where each of its three readers hands it back, the kernel once
        tol = 2.0 ** -6 if low and name == "x" else 2e-5
        np.testing.assert_allclose(np.asarray(g, np.float32), w, rtol=0,
                                   atol=tol * np.abs(w).max(), err_msg=name)


def _stream_sized(eqn, tokens, c):
    """Elements / (tokens x c) of every operand and result of ``eqn`` that
    is at least one stream wide."""
    return [int(np.prod(v.aval.shape)) / (tokens * c)
            for v in (*eqn.invars, *eqn.outvars)
            if getattr(getattr(v, "aval", None), "shape", None) is not None
            and int(np.prod(v.aval.shape)) >= tokens * c]


def test_a_sublayer_moves_its_streams_in_four_kernels():
    """The design's count, held on the CPU: in the jaxpr of one
    ``HyperConnected`` sublayer's value and gradient every equation with an
    operand the size of a stream is one of the four kernels, or a reshape
    between [.., n·C], [.., n, C] and [tokens, n·C] (which copies nothing:
    tokens stay rows, a stream's columns stay together), and the kernels'
    stream-sized operands come to 14 stream-widths forward (5 + 9) and 27
    backward (14 + 13). A change that makes XLA read the streams once more
    fails here, not on the chip."""
    cfg = JoyaiConfig(**dict(SMALL, hidden_size=64))
    # 128 tokens: more than Phi's 24 n rows, so Phi is no stream's size
    n, c, lead = cfg.hc_mult, cfg.hidden_size, (2, 64)
    tokens = lead[0] * lead[1]
    pt.seed(3)
    layer = HyperConnected(cfg)
    state = nn.get_state(layer)
    x = jnp.ones((*lead, n * c), jnp.float32)

    def path(x, params):
        (out, _, err), _ = nn.functional_call(
            layer, {"params": params, "buffers": state["buffers"]}, x,
            lambda u: u)                    # the sublayer adds no equation
        return out, err

    def both_ways(x, params, ct):
        out, back = jax.vjp(path, x, params)
        return out, back((ct, jnp.ones((), jnp.float32)))

    jaxpr = jax.make_jaxpr(both_ways)(x, state["params"], x)
    moved, others = {}, []

    def walk(eqns):
        for eqn in eqns:
            wide = _stream_sized(eqn, tokens, c)
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                moved[name] = moved.get(name, 0) + sum(wide)
            elif eqn.primitive.name == "scan":
                continue    # the Sinkhorn steps' stack: 20 x [tokens, 16]
            elif any(hasattr(v, "jaxpr") or hasattr(v, "eqns")
                     for v in eqn.params.values()):
                for v in eqn.params.values():   # pjit, custom_vjp, remat
                    inner = getattr(v, "jaxpr", v)
                    if hasattr(inner, "eqns"):
                        walk(inner.eqns)
            elif wide and eqn.primitive.name != "reshape":
                others.append((eqn.primitive.name, wide))

    walk(jaxpr.jaxpr.eqns)
    assert not others, others
    assert moved == {"hc_pre_fwd": 5, "hc_post_fwd": 9, "hc_post_bwd": 14,
                     "hc_pre_bwd": 13}, moved
    assert sum(moved.values()) <= 41


def test_compiled_step_scopes_the_backward_kernels_too():
    """``harness/scopes.scope_of_ops`` on a small model's
    ``Trainer.compiled_text`` (CPU: the kernels interpreted, their
    operations inlined under the kernels' names): the backward kernels'
    operations belong to ``pt.hc.collect`` and ``pt.hc.scatter`` as the
    forward ones do — ``mhc_hbm_roofline`` divides by the time under those
    two — and nothing shaped like a stream ([.., C] or [.., n·C] over all
    the tokens) belongs to ``pt.hc.map``."""
    from harness import scopes

    cfg = JoyaiConfig(**SMALL, held=(0, 8), recompute="blocks")
    pt.seed(4)
    trainer = Trainer(Joyai(cfg), optimizer.AdamW(1e-3, weight_decay=0.1),
                      next_token_loss, amp=True)
    ids, labels = _batch(cfg, 2, 6)
    text = trainer.compiled_text(ids, labels)
    scope_of = scopes.scope_of_ops(text)
    names = {}
    for line in text.splitlines():
        found = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s", line)
        op_name = re.search(r'op_name="([^"]*)"', line)
        if found and op_name:
            names[found.group(1)] = (found.group(2), op_name.group(1))
    for kernel, scope in (("hc_pre_fwd", "pt.hc.collect"),
                          ("hc_pre_bwd", "pt.hc.collect"),
                          ("hc_post_fwd", "pt.hc.scatter"),
                          ("hc_post_bwd", "pt.hc.scatter")):
        inside = [k for k, (_, op) in names.items() if f"/{kernel}/" in op]
        assert inside, kernel
        assert {scope_of[k] for k in inside} == {scope}, kernel
    tokens = ids.shape[0] * ids.shape[1]
    widths = {cfg.hidden_size, cfg.hc_mult * cfg.hidden_size}
    for k, (shape, _) in names.items():
        if scope_of.get(k) != "pt.hc.map":
            continue
        for dims in re.findall(r"\[([\d,]+)\]", shape):
            dims = [int(d) for d in dims.split(",")]
            assert not (dims[-1] in widths
                        and int(np.prod(dims)) >= tokens * cfg.hidden_size
                        ), (k, shape)


# -- YaRN -----------------------------------------------------------------


def test_yarn_frequencies_and_softmax_gain_by_hand():
    """The 32 frequencies of the published ``rope_scaling``: pairs 0..10
    as plain rotary, 23..31 divided by 64, the ramp (i - 10) / 13 between;
    ``m`` = 0.1 ln 64 + 1 = 1.41589, ``m²`` = 2.00474."""
    f = yarn_frequencies(10000.0, 64, YARN)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # the two bounds: 64 ln(4096 / (2 pi beta)) / (2 ln 10000)
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32))
                      / (2 * math.log(10000))) == 10
    assert math.ceil(64 * math.log(4096 / (2 * math.pi * 1))
                     / (2 * math.log(10000))) == 23
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    np.testing.assert_allclose(f, plain * ((1 - ramp) + ramp / 64),
                               rtol=1e-14)
    np.testing.assert_array_equal(f[:11], plain[:11])
    np.testing.assert_allclose(f[23:], plain[23:] / 64, rtol=1e-14)
    assert abs(f[16] / plain[16] - (1 - 6 / 13 + 6 / 13 / 64)) < 1e-14
    np.testing.assert_allclose(REF.yarn_frequencies(10000.0, 64, YARN), f,
                               rtol=1e-14)
    assert abs(yarn_mscale(64, 1) - 1.4158883) < 1e-6
    cfg = JoyaiConfig(**WIDTHS)
    assert abs(cfg.softmax_gain - 2.00474) < 1e-5
    assert JoyaiConfig().softmax_gain == 1.0
    assert yarn_mscale(1, 1) == 1.0


def test_no_scaling_is_todays_rotary_bit_for_bit():
    """``rope_scaling: null``: ``rotary_pairs`` is the function it was
    (PR 50's text, kept here), bit for bit; YaRN differs from it."""
    def before(x, theta):
        L, D = x.shape[1], x.shape[-1]
        inv_freq = float(theta) ** (-np.arange(0, D, 2, dtype=np.float64) / D)
        angle = np.arange(L, dtype=np.float64)[:, None] * inv_freq[None, :]
        cos = jnp.asarray(np.repeat(np.cos(angle), 2, axis=1),
                          jnp.float32)[None, :, None]
        sin = jnp.asarray(np.repeat(np.sin(angle), 2, axis=1),
                          jnp.float32)[None, :, None]
        pairs = x.reshape(*x.shape[:-1], D // 2, 2)
        turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1)
        return x * cos + turned.reshape(x.shape) * sin

    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 40, 3, 64)),
                    jnp.float32)
    for theta in (10000.0, 32000000.0):
        np.testing.assert_array_equal(np.asarray(rotary_pairs(x, theta)),
                                      np.asarray(before(x, theta)))
        np.testing.assert_array_equal(
            np.asarray(rotary_pairs(x, theta, None)),
            np.asarray(before(x, theta)))
    scaled = rotary_pairs(x, 10000.0, YARN)
    assert float(jnp.max(jnp.abs(scaled - before(x, 10000.0)))) > 0.1
    np.testing.assert_allclose(
        np.asarray(scaled), np.asarray(REF._rotary_pairs(x, 10000.0, YARN)),
        rtol=0, atol=1e-6)
    # position 0 turns nothing; norms are kept (mscale / mscale_all_dim = 1)
    np.testing.assert_array_equal(np.asarray(scaled[:, 0]),
                                  np.asarray(x[:, 0]))
    np.testing.assert_allclose(np.asarray(jnp.sum(scaled ** 2, -1)),
                               np.asarray(jnp.sum(x ** 2, -1)), rtol=1e-5)


# -- the held share -----------------------------------------------------------


def _layer_case(seed=0, T=48, h=32, f=16, E=16):
    rng = np.random.default_rng(seed)
    p = {"router_w": rng.normal(size=(h, E)) * 0.5,
         "w_gate": rng.normal(size=(E, h, f)) * 0.2,
         "w_up": rng.normal(size=(E, h, f)) * 0.2,
         "w_down": rng.normal(size=(E, f, h)) * 0.2,
         "shared.w_gate": rng.normal(size=(h, f)) * 0.2,
         "shared.w_up": rng.normal(size=(h, f)) * 0.2,
         "shared.w_down": rng.normal(size=(f, h)) * 0.2}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    x = jnp.asarray(rng.normal(size=(T, h)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.05, jnp.float32)
    return p, x, bias


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's share test at Xing4.0's ratios: 16 experts in 8 shares
    of 2, 4 a token, scale 2 — the routed parts the eight shares give, with
    the shared expert counted ONCE, equal the uncut reference's layer."""
    p, x, bias = _layer_case()
    k, E, scale = 4, 16, 2.0
    cfg = {"num_experts_per_tok": k, "held_first": 0, "n_routed_experts": E,
           "routed_scaling_factor": scale}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(REF._experts(p, "", x, bias, cfg, None,
                                       lambda a: a)[0])
        shared = REF._swiglu(x, p["shared.w_gate"], p["shared.w_up"],
                             p["shared.w_down"], lambda a: a)
        parts, landed = [], 0
        for first in range(0, E, 2):
            out, route = moe.held_moe(
                x, p["router_w"], bias, p["w_gate"][first:first + 2],
                p["w_up"][first:first + 2], p["w_down"][first:first + 2],
                k, (first, 2), scale)
            parts.append(np.asarray(out))
            landed += int(route["held_assignments"])
            assert int(route["dropped"]) == 0
    assert landed == x.shape[0] * k       # every assignment lands once
    assert all(np.max(np.abs(part)) > 0 for part in parts)
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), want,
                               rtol=1e-5, atol=1e-5)


# -- counts -----------------------------------------------------------------


def _allocated(cfg):
    shapes = jax.eval_shape(lambda: nn.get_state(Joyai(cfg))["params"])
    return sum(int(np.prod(s.shape)) for s in shapes.values())


@pytest.mark.parametrize("sizes,want", [
    (dict(vocab_size=131072, num_layers=40, first_dense=2, held=(0, 64)),
     29_505_502_832),
    (dict(vocab_size=16384, num_layers=5, first_dense=1, held=(0, 8)),
     759_346_190)], ids=["whole", "cut"])
def test_parameter_count_is_the_allocated_models(sizes, want):
    """The published 29B without the prediction module, and the cell's cut
    — against the shapes the model allocates (``jax.eval_shape``: no
    array is made) and ISSUE 51's arithmetic."""
    cfg = JoyaiConfig(**WIDTHS, **sizes)
    assert cfg.parameter_count() == want == _allocated(cfg)


def test_parameter_count_with_a_module_and_one_stream():
    """JoyAI's own configuration: the prediction module counted, no
    residual-path parameters."""
    cfg = JoyaiConfig(vocab_size=211, hidden_size=32, num_heads=4,
                      num_layers=3, dense_size=48, q_rank=24, kv_rank=16,
                      nope_dim=16, rope_dim=8, v_dim=16, num_experts=8,
                      experts_per_token=2, expert_size=16, held=(2, 2))
    assert cfg.parameter_count() == _allocated(cfg)


def test_benchmark_flop_and_byte_counts_by_hand():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    part = FLOPS.block_flops_per_token(cfg, 4096)
    proj = 2 * (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
                + 32 * 128 * 3584)
    core = 2 * 32 * (192 + 128) * 4097 / 2
    assert part["attention"] == proj + core == 56_819_712 + 41_953_280
    # a sublayer's path: the projection, the collect, the scatter
    path = 2 * (14336 * 24 + 4 * 3584 + 4 * 5 * 3584)
    assert FLOPS.residual_path_flops_per_token(cfg) == path == 860_160
    assert part["residual_path"] == 2 * path
    assert part["dense_ffn"] == 6 * 3584 * 9216
    expert = 6 * 3584 * 1024
    assert part["expert_ffn"] == 2 * 3584 * 64 + expert + 4 * 8 / 64 * expert
    assert part["head"] == 2 * 3584 * 16384
    expert_block = part["attention"] + part["residual_path"] \
        + part["expert_ffn"]
    dense_block = part["attention"] + part["residual_path"] \
        + part["dense_ffn"]
    assert round(expert_block / 1e6, 1) == 134.0
    assert round(dense_block / 1e6, 1) == 298.7
    forward = FLOPS.forward_flops_per_token(cfg, 4096)
    assert forward == dense_block + 4 * expert_block + part["head"]
    assert round(forward / 1e6, 1) == 952.0
    assert FLOPS.train_flops_per_token(cfg, 4096) == 3 * forward
    # the residual path is 1.3% of an expert block; as the step routed
    assert round(part["residual_path"] / expert_block, 3) == 0.013
    more = FLOPS.forward_flops_per_token(cfg, 4096, held_per_token=1.0)
    assert more - forward == 4 * 0.5 * expert
    # bytes: (2n + 2) C elements a sublayer each way, float32
    assert BYTES.sublayer_bytes_per_token(cfg) == (2 * 4 + 2) * 3584 * 4
    assert BYTES.train_bytes_per_token(cfg) == 2 * 10 * 143_360


def test_configuration_file_keeps_the_published_widths():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu",
        "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 32, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (5, 1, 8, 16384, 0)
    assert cfg["published"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2,
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1}
    assert cfg["router_width"] == 64 and cfg["held_first"] == 0
    assert cfg["recompute"] == "blocks"
    for key in ("deployment", "parameters", "distortion", "assumed",
                "departures"):
        assert cfg[key], key
    assert "759,346,190" in cfg["parameters"]
    # the model the adapter builds from it is the one counted
    adapter = _load("_mhc_adapter", "adapters", "causal_mhc_mla_moe_lm.py")
    model_cfg = adapter._model_cfg(cfg)
    assert model_cfg.parameter_count() == 759_346_190
    assert model_cfg.total_layers == 40 and model_cfg.hc_mult == 4
    assert abs(model_cfg.out_std - 0.006 / math.sqrt(80)) < 1e-12


# -- what is refused ---------------------------------------------------------


@pytest.mark.parametrize("bad,says", [
    (dict(num_mtp=2), "one prediction module"),
    (dict(num_mtp=1), "no prediction module"),
    (dict(hc_mult=0), "plain block"),
    (dict(rope_scaling={"type": "linear", "factor": 4}), "yarn"),
    (dict(recompute="experts"), "none or blocks"),
    (dict(n_group=2), "group-limited")])
def test_what_the_model_cannot_run_is_refused(bad, says):
    with pytest.raises(EnforceNotMet, match=says):
        Joyai(JoyaiConfig(**dict(SMALL, **bad)))


def test_an_unknown_scaling_is_refused_by_the_rotary_too():
    x = jnp.ones((1, 4, 1, 8), jnp.float32)
    with pytest.raises(EnforceNotMet, match="yarn or none"):
        rotary_pairs(x, 10000.0, {"type": "dynamic", "factor": 2})


# -- the cell's correct ---------------------------------------------------


def _f32_got(model, state, ids, labels):
    def total(params):
        logits, new = nn.functional_call(
            model, {"params": params, "buffers": state["buffers"]},
            jnp.asarray(ids), training=True)
        return next_token_loss(logits, jnp.asarray(labels)), (
            logits, new["buffers"])

    with jax.default_matmul_precision("highest"):
        (loss, (logits, buffers)), grads = jax.value_and_grad(
            total, has_aux=True)(state["params"])
    return _got(float(loss), grads, buffers, logits)


@pytest.fixture(scope="module")
def planted_case():
    pt.seed(9)
    cfg = JoyaiConfig(**SMALL, held=(0, 8))
    model = Joyai(cfg)
    ids, labels = _batch(cfg, 2, 4)
    state = jax.tree_util.tree_map(jnp.array, nn.get_state(model))
    ref = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg),
                             buffers=state["buffers"])
    return model, state, ids, labels, ref


def _plant(monkeypatch, model, fault):
    """``fault`` in the program ``model`` runs, for the context returned.
    The configuration's faults as the chip's control plants them; the
    three of the residual path on the names ``HyperConnected`` calls since
    PR 52 — ``hc_post`` for the control's ``hc_scatter``, ``hc_gates`` and
    ``hc_pre`` for its ``hc_mappings``, which the fused path no longer
    calls (the control plants on ``transformer.hc_scatter`` /
    ``transformer.hc_mappings`` and is a ``benchmark`` PR's to move)."""
    sound_post, sound_gates = transformer.hc_post, transformer.hc_gates
    if fault == "h_res_transposed":
        monkeypatch.setattr(
            transformer, "hc_post", lambda x, y, h_post, h_res: sound_post(
                x, y, h_post, jnp.swapaxes(h_res, -1, -2)))
    elif fault == "h_post_without_its_2":
        def halved(*args):
            h_pre, h_post, h_res = sound_gates(*args)
            return h_pre, 0.5 * h_post, h_res

        monkeypatch.setattr(transformer, "hc_gates", halved)
    elif fault == "mappings_in_bf16":
        # the control's own bf16 mappings, handed over as the fused path
        # takes them: u of its H_pre, and its H_post / H_res in place of
        # what hc_gates makes of z
        made = []

        def pre(x, phi, b, alpha, rms_eps):
            cfg = model.cfg
            made.append(CONTROL.mappings_in_bf16(
                x, phi, b, alpha, cfg.hc_sinkhorn_iters, cfg.hc_eps,
                cfg.hc_clamp, rms_eps))
            return hc_collect(x, made[-1][0]), None, x

        monkeypatch.setattr(transformer, "hc_pre", pre)
        monkeypatch.setattr(transformer, "hc_gates", lambda *args: made.pop())
    else:
        return CONTROL.planted(model, fault)
    return contextlib.nullcontext()


@pytest.mark.parametrize("fault", [f for f in CONTROL.FAULTS
                                   if f != "reference_in_float8"])
def test_planted_faults_read_not_correct(planted_case, monkeypatch, fault):
    """The five wrong programs ISSUE 51 names, planted as the chip's
    control plants them (``benchmarks/tests/mhc_fault_control.py``; the
    residual path's on the fused path's names, ``_plant``), at the
    model's INITIAL mappings (``alpha`` 0.01, ``b_res`` as
    ``hc_res_bias_init``): each is refused by the float32 limits, the
    sound program is not."""
    model, state, ids, labels, ref = planted_case
    with _plant(monkeypatch, model, fault):
        got = _f32_got(model, state, ids, labels)
    out = REF.compare(got, ref, "f32")
    assert out["ok"] == (fault == "none"), (fault, out)
    if fault == "one_sinkhorn_step":
        assert out["hc_abs"] > 1e-2


def test_the_reference_in_float8_is_refused_by_the_amp_limits(planted_case):
    model, state, ids, labels, ref = planted_case
    low = REF.loss_and_grads(
        state["params"], ids, labels, _ref_cfg(model.cfg),
        buffers=state["buffers"], expert_index=ref["expert_index"],
        operand_dtype=jnp.float8_e4m3fn)
    low.pop("logits")
    out = REF.compare(low, ref, "amp")
    assert not out["ok"] and out["grad_leaf_rel"] > REF.TOL["amp"][
        "grad_leaf_rel"]


def test_host_span_and_counter_names():
    assert {"pt.hc.map", "pt.hc.collect", "pt.hc.scatter"} <= set(
        profiler.DEVICE_SCOPES)
    pt.seed(1)
    cfg = JoyaiConfig(**SMALL, held=(2, 4))
    model = Joyai(cfg)
    ids, _ = _batch(cfg, 1, 0)
    profiler.start_timeline()
    jax.eval_shape(lambda s, i: nn.functional_call(model, s, i)[0],
                   nn.get_state(model), jnp.asarray(ids))
    spans = [s.counts for s in profiler.host_spans()
             if s.name == "pt.hc.layers"]
    assert spans == [{"layers": 3, "streams": 4, "sinkhorn_iters": 20,
                      "sublayers": 6}]
    assert "hc_res_err" in nn.get_state(model)["buffers"]
    # a one-stream model has neither
    plain = Joyai(JoyaiConfig(**dict(SMALL, hc_mult=1, num_mtp=1),
                              held=(2, 4)))
    assert "hc_res_err" not in nn.get_state(plain)["buffers"]


@pytest.mark.parametrize("mode, floor", [("f32", 1e-5), ("amp", 1e-4)])
def test_a_leaf_that_cancels_is_judged_against_the_modes_floor(mode, floor):
    """A leaf whose largest entry lies under ``GRADIENT_FLOOR[mode]`` (an
    ``alpha`` whose per-token terms cancel: 3.6e-5 at seed 5100000303 on
    the chip, with 1.1e-5 of the step's rounding in it) is judged against
    the floor, a leaf above it against its own largest entry."""
    assert REF.GRADIENT_FLOOR[mode] == floor
    small, large = np.float32(floor * 0.36), np.float32(floor * 40)
    ref = {"loss": 1.0, "total": 1.0, "hc_res_err": 1e-6,
           "grads": {"alpha": jnp.array([small, 0.0, 0.0]),
                     "w": jnp.array([[large, large / 2]])}}
    err = np.float32(floor * 0.11)
    got = {"loss": 1.0, "total": 1.0, "hc_res_err": 1e-6,
           "grads": {"alpha": ref["grads"]["alpha"] + err,
                     "w": ref["grads"]["w"] + err}}
    out = REF.compare(got, ref, mode)
    assert out["worst_leaf"] == "alpha"
    np.testing.assert_allclose(out["grad_leaf_rel"], 0.11, rtol=1e-3)
    np.testing.assert_allclose(out["worst_leaves"]["w"][1], 0.11 / 40,
                               rtol=1e-3)
    assert out["ok"] == (0.11 <= REF.TOL[mode]["grad_leaf_rel"])


def test_reference_compiled_ahead_is_the_reference():
    """``REF.lowered(...).compile(...)`` handed to ``REF.adopt`` — how the
    cell's check compiles the reference beside its other two programs, on
    a thread of its own and with the compiler's least effort — serves
    ``loss_and_grads`` with and without a given routing, to the numbers of
    the jitted function, and compiles nothing more."""
    pt.seed(5)
    cfg = JoyaiConfig(**dict(SMALL, num_layers=2), held=(2, 4))
    # a key of its own among the reference's jitted functions
    ref_cfg = dict(_ref_cfg(cfg), hc_eps=cfg.hc_eps * (1 + 2 ** -20))
    state = nn.get_state(Joyai(cfg))
    ids, labels = _batch(cfg, 2, 1)
    plain = REF.loss_and_grads(state["params"], ids, labels, ref_cfg,
                               buffers=state["buffers"])
    REF._COMPILED.pop(REF._key(ref_cfg, None))
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(1) as pool:
        compiled = pool.submit(
            REF.lowered(state["params"], ids, labels, ref_cfg,
                        buffers=state["buffers"]).compile,
            compiler_options={"exec_time_optimization_effort": -1.0}
        ).result()
    REF.adopt(ref_cfg, compiled)
    try:
        assert REF._value_and_grad(ref_cfg) is compiled
        for index in (None, plain["own_index"]):
            ahead = REF.loss_and_grads(state["params"], ids, labels, ref_cfg,
                                       expert_index=index,
                                       buffers=state["buffers"])
            assert abs(ahead["loss"] - plain["loss"]) <= 1e-6
            for k, g in plain["grads"].items():
                np.testing.assert_allclose(ahead["grads"][k], g, rtol=1e-5,
                                           atol=1e-8, err_msg=k)
    finally:
        REF._COMPILED.pop(REF._key(ref_cfg, None))


def test_cell_rehearses_end_to_end():
    """``benchmarks/run.py --workload xing4_29b_a4b_seq4096 --rehearse``:
    the harness, the adapter, the reference and every reader, on the CPU
    at the rehearsal's sizes."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "xing4_29b_a4b_seq4096", "--seed", "3000000019",
         "--seconds", "1", "--trace", "0", "--rehearse"],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["failed"] == 0, out.stderr[-3000:]
    assert set(line["metrics"]) == {"rehearsal.tokens_per_s_per_chip",
                                    "rehearsal.setup_s"}
    numbers = line["reference"]["numbers"]
    for name in ("f32.grad_leaf_rel", "f32.logit_rel", "f32.hc_abs",
                 "amp.grad_leaf_rel", "amp.hc_abs", "update.param_rel",
                 "forms.ok"):
        assert name in numbers, name

"""OLMoE on the dense path: ``models.Olmoe`` through ``executor.Trainer``
against the plain reference that sits beside the benchmark's
configuration, the dropless expert path against the mask-every-expert
form under skewed routing, the auxiliary losses through the train step,
rotary and QK-norm against closed forms, and the causal flash kernel at
head 128 (interpret mode) against einsum."""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.executor import Trainer, auxiliary_loss, make_train_step
from paddle_tpu.models import Olmoe, OlmoeConfig
from paddle_tpu.models.transformer import rotary
from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.ring_attention import local_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LR = 2.0 ** 16


def _load(name, *parts):
    path = os.path.join(ROOT, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("_olmoe_reference", "configs", "olmoe-1b-7b.reference.py")

#: (model configuration, the same sizes under the reference's keys)
SMALL = dict(vocab_size=97, hidden_size=32, num_heads=4, num_layers=2,
             num_experts=8, experts_per_token=2, expert_size=16,
             max_seq_len=16)
#: the published ratios (64 experts, 8 a token) at tiny widths
RATIOS = dict(vocab_size=61, hidden_size=16, num_heads=2, num_layers=1,
              num_experts=64, experts_per_token=8, expert_size=8,
              max_seq_len=12)


def _ref_cfg(cfg: OlmoeConfig):
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_experts_per_tok": cfg.experts_per_token,
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "router_aux_loss_coef": cfg.lb_coef,
            "router_z_loss_coef": cfg.z_coef}


def _batch(cfg: OlmoeConfig, batch: int, seed: int):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len + 1),
                        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def _sgd_grads(model, ids, labels, amp=False):
    """Loss and gradients as the TRAIN STEP computes them: SGD, so
    gradient = (parameters before - parameters after) / lr. lr is a large
    power of two: the division is exact, and a norm weight of 1.0 does not
    swallow a gradient of 1e-3 in the subtraction's rounding."""
    state = jax.tree_util.tree_map(jnp.array, nn.get_state(model))
    opt = optimizer.SGD(learning_rate=LR)
    step = make_train_step(model, opt, nn.functional.cross_entropy,
                           donate=False, amp=amp)
    new_state, _, loss = step(state, opt.init(state["params"]),
                              jax.random.key(0), (jnp.asarray(ids),),
                              (jnp.asarray(labels),))
    grads = {k: (np.asarray(state["params"][k]) - np.asarray(v)) / LR
             for k, v in new_state["params"].items()}
    return float(loss), grads, new_state["buffers"], state["params"]


@pytest.mark.parametrize("sizes", [SMALL, RATIOS], ids=["small", "ratios"])
def test_train_step_matches_reference(sizes):
    """Loss and EVERY gradient leaf of the step, float32, against the
    reference at 1e-5: the same function by another route (sorted rows and
    grouped matmuls against all-experts-masked); only summation order
    differs, and CPU float32 is exact to ~1e-7 a sum."""
    pt.seed(3)
    cfg = OlmoeConfig(**sizes)
    model = Olmoe(cfg)
    ids, labels = _batch(cfg, 2, 5)
    loss, grads, buffers, params = _sgd_grads(model, ids, labels)
    ref = REF.loss_and_grads(params, ids, labels, _ref_cfg(cfg))
    assert set(grads) == set(ref["grads"])
    assert abs(loss - ref["loss"]) <= 1e-5 * abs(ref["loss"])
    for name, r in ref["grads"].items():
        top = np.max(np.abs(r))
        assert top > 0, name
        assert np.max(np.abs(grads[name] - r)) <= 1e-5 * top, name
    T = ids.size
    counts = np.asarray(buffers["expert_counts"])
    assert counts.shape == (cfg.num_layers, cfg.num_experts)
    assert (counts.sum(axis=1) == T * cfg.experts_per_token).all()
    assert int(buffers["tokens_dropped"]) == 0
    assert abs(float(buffers["aux_loss"]) - ref["aux"]) <= 1e-5 * ref["aux"]


def test_trainer_trains_and_reports_task_loss():
    """Through ``Trainer`` (donated step, Adam, amp): the loss returned is
    the task loss and falls; counters stay shape-stable (one compile)."""
    pt.seed(0)
    cfg = OlmoeConfig(**SMALL)
    model = Olmoe(cfg)
    ids, labels = _batch(cfg, 4, 1)
    tr = Trainer(model, optimizer.AdamW(learning_rate=3e-3, weight_decay=0.1,
                                        beta2=0.95),
                 nn.functional.cross_entropy, amp=True)
    first = float(tr.train_step(ids, labels))
    for _ in range(30):
        last = float(tr.train_step(ids, labels))
    assert abs(first - math.log(cfg.vocab_size)) < 0.2     # not loss + aux
    assert last < 0.7 * first
    assert tr._train_step._cache_size() == 1
    assert int(tr.state["buffers"]["tokens_dropped"]) == 0
    assert "pt.moe.experts" in tr.compiled_text(ids, labels)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_amp_step_is_bf16_in_experts_and_f32_in_router():
    """``amp`` reaches the grouped matmuls (all nine kernels of a step:
    three banks x three passes, bf16 operands) and leaves the router
    float32 at the highest precision, forward and both gradients."""
    pt.seed(0)
    cfg = OlmoeConfig(**dict(SMALL, num_layers=1))
    model = Olmoe(cfg)
    ids, labels = _batch(cfg, 2, 1)
    state = nn.get_state(model)
    opt = optimizer.SGD(learning_rate=1.0)
    step = make_train_step(model, opt, nn.functional.cross_entropy,
                           donate=False, amp=True)
    jaxpr = jax.make_jaxpr(step)(state, opt.init(state["params"]),
                                 jax.random.key(0), (jnp.asarray(ids),),
                                 (jnp.asarray(labels),))
    kernels = [e for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 9
    for e in kernels:
        floats = [v.aval.dtype for v in e.invars
                  if jnp.issubdtype(v.aval.dtype, jnp.floating)]
        assert floats and all(d == jnp.bfloat16 for d in floats), e
    router = [e for e in _eqns(jaxpr.jaxpr)
              if e.primitive.name == "dot_general"
              and "HIGHEST" in str(e.params["precision"])]
    assert len(router) == 3
    for e in router:
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)
        assert any(cfg.num_experts in v.aval.shape for v in e.invars)


def _skewed_case(T=96, d=16, E=64, k=8, f=8, seed=0):
    """Routing skewed: feature 0 is the constant 3 and pushes experts
    40..59 out of every token's reach; 60% of the tokens share a component
    that expert 5 answers to. (A token names k DISTINCT experts, so one
    expert holds at most 1/k of the assignments: "most loaded" is counted
    in tokens — expert 5 is chosen by more than 40% of them, > 3.2x the
    mean load.)"""
    r = np.random.default_rng(seed)
    x = r.normal(size=(T, d)).astype(np.float32)
    x[: int(0.6 * T)] += 2.0
    x[:, 0] = 3.0
    router = (r.normal(size=(d, E)) * 0.1).astype(np.float32)
    router[1:, 5] += 0.5
    router[0, 40:60] = -5.0
    banks = [(r.normal(size=s) * 0.3).astype(np.float32)
             for s in ((E, d, f), (E, d, f), (E, f, d))]
    return [jnp.asarray(a) for a in (x, router, *banks)], k


def _mask_every_expert(x, router, w_gate, w_up, w_down, k):
    z = x @ router
    p = jax.nn.softmax(z, axis=-1)
    _, idx = jax.lax.top_k(p, k)
    mask = jnp.sum(jax.nn.one_hot(idx, p.shape[-1]), axis=1)
    act = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_gate)) * jnp.einsum(
        "td,edf->tef", x, w_up)
    return jnp.einsum("tef,efd->td", act * (mask * p)[..., None], w_down)


def test_dropless_equals_mask_every_expert_under_skew():
    args, k = _skewed_case()
    out, route = jax.jit(lambda *a: moe.dropless_moe(*a, k))(*args)
    counts = np.asarray(route["counts"])
    assert counts.sum() == args[0].shape[0] * k
    assert counts[5] > 0.4 * args[0].shape[0]
    assert (counts[40:60] == 0).all()
    assert int(route["dropped"]) == 0
    want = _mask_every_expert(*args, k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # gradients too: the custom VJPs of the permutations are gathers
    f = lambda *a: jnp.sum(jnp.sin(moe.dropless_moe(*a, k)[0]))
    g = lambda *a: jnp.sum(jnp.sin(_mask_every_expert(*a, k)))
    got = jax.grad(f, argnums=(0, 1, 2, 3, 4))(*args)
    ref = jax.grad(g, argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, ref):
        b = np.asarray(b)
        assert np.max(np.abs(np.asarray(a) - b)) <= 5e-5 * np.max(np.abs(b))


def test_auxiliary_losses_reach_the_router_gradient():
    """The router losses move the router's gradient THROUGH the train
    step; with both coefficients 0 the step's gradient is the task
    loss's. Expert banks see no router loss either way."""
    ids = labels = None
    grads = {}
    for name, coefs in (("on", {}), ("off", dict(lb_coef=0.0, z_coef=0.0))):
        pt.seed(7)
        cfg = OlmoeConfig(**dict(SMALL, **coefs))
        model = Olmoe(cfg)
        ids, labels = _batch(cfg, 2, 2)
        _, grads[name], buffers, params = _sgd_grads(model, ids, labels)
        if name == "off":
            assert float(buffers["aux_loss"]) == 0.0

            def task(p):
                out, _ = nn.functional_call(
                    model, {"params": p, "buffers": nn.get_state(model)[
                        "buffers"]}, jnp.asarray(ids), training=True)
                return nn.functional.cross_entropy(out, jnp.asarray(labels))

            want = jax.grad(task)(params)
            for k, g in grads["off"].items():
                np.testing.assert_allclose(g, np.asarray(want[k]), rtol=1e-4,
                                           atol=1e-7 + 1e-5 * np.max(np.abs(g)))
    router = "blocks.0.moe.router_w"
    moved = np.max(np.abs(grads["on"][router] - grads["off"][router]))
    assert moved > 0.05 * np.max(np.abs(grads["off"][router]))
    # the last layer's experts sit below no router: no router loss in them
    bank = "blocks.1.moe.w_down"
    np.testing.assert_allclose(grads["on"][bank], grads["off"][bank],
                               rtol=1e-5, atol=1e-9)


def test_auxiliary_loss_rule():
    assert auxiliary_loss({"bn.mean": jnp.ones(3)}) is None
    got = auxiliary_loss({"a.aux_loss": jnp.asarray(1.5), "aux_loss":
                          jnp.asarray(2.0), "b.aux_loss_total": jnp.ones(())})
    assert float(got) == 3.5


def test_rotary_closed_form():
    """Pair (i, i + D/2) of position t turns by t * theta^(-2i/D):
    position 0 is untouched, norms are kept, and q.k depends on the
    positions' difference only."""
    r = np.random.default_rng(0)
    D, theta = 8, 10000.0
    x = jnp.asarray(r.normal(size=(1, 6, 2, D)), jnp.float32)
    y = np.asarray(rotary(x, theta))
    np.testing.assert_allclose(y[:, 0], np.asarray(x)[:, 0], atol=1e-7)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    t, i = 5, 1
    ang = t * theta ** (-2.0 * i / D)
    a, b = float(x[0, t, 0, i]), float(x[0, t, 0, i + D // 2])
    assert abs(y[0, t, 0, i] - (a * math.cos(ang) - b * math.sin(ang))) < 1e-5
    assert abs(y[0, t, 0, i + D // 2]
               - (b * math.cos(ang) + a * math.sin(ang))) < 1e-5
    same = jnp.broadcast_to(x[:, :1], x.shape)      # one vector everywhere
    z = np.asarray(rotary(same, theta))[0, :, 0]
    np.testing.assert_allclose(z[1] @ z[3], z[2] @ z[4], rtol=1e-4)
    np.testing.assert_allclose(z[0] @ z[2], z[3] @ z[5], rtol=1e-4)


def test_qk_norm_is_over_the_whole_projection():
    """``q_norm`` divides by the RMS of all ``hidden`` columns before the
    split into heads, not head by head."""
    pt.seed(1)
    cfg = OlmoeConfig(**dict(SMALL, num_layers=1, attn_impl="einsum"))
    model = Olmoe(cfg)
    attn = model.blocks[0].attn
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 4, 32)),
                    jnp.float32)
    q = np.asarray(x) @ np.asarray(attn.wq)
    want = q / np.sqrt((q * q).mean(-1, keepdims=True) + cfg.rms_eps)
    np.testing.assert_allclose(np.asarray(attn.q_norm(x @ attn.wq)), want,
                               rtol=1e-5, atol=1e-6)
    per_head = q.reshape(1, 4, 4, 8)
    per_head = per_head / np.sqrt((per_head ** 2).mean(-1, keepdims=True))
    assert np.max(np.abs(per_head.reshape(q.shape) - want)) > 1e-2


def test_flash_causal_head128_matches_einsum_forward_and_backward():
    """The kernel path the block takes on the chip — ``causal=True``,
    D = 128 (no pad to the lane width) — in interpret mode, float32
    operands, against einsum attention: output and all three gradients."""
    r = np.random.default_rng(0)
    q, k, v = (jnp.asarray(r.normal(size=(1, 256, 2, 128)) * 0.5,
                           jnp.float32) for _ in range(3))
    w = jnp.asarray(r.normal(size=(1, 256, 2, 128)), jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True,
        precision="highest")
    plain = lambda q, k, v: local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(plain(q, k, v)), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_benchmark_flop_counts_by_hand():
    """``benchmarks/harness/flops_moe.py`` against the hand sum of ISSUE
    26 for the cell's configuration at L = 4096. Forward a token:
    projections 4 * 2*2048*2048 = 33,554,432; causal scores and values
    2*2048*4097 = 16,781,312; router 2*2048*64 = 262,144; the 8 experts
    8 * 3 * 2*2048*1024 = 100,663,296; head 2*2048*50304 = 206,045,184;
    sum 357,306,368; x3 passes."""
    flops = _load("_flops_moe", "harness", "flops_moe.py")
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    assert (33_554_432 + 16_781_312 + 262_144 + 100_663_296
            + 206_045_184) * 3 == 1_071_919_104
    assert flops.causal_moe_train_flops_per_token(cfg, 4096) == 1_071_919_104
    assert flops.expert_matmul_flops_per_token(cfg) == 3 * 100_663_296
    # the step's grouped matmuls at 8192 tokens: 3 passes x 3 matrices
    # x 2*2048*1024 x 8192*8
    assert flops.expert_matmul_flops_per_token(cfg) * 8192 == \
        3 * 3 * 2 * 2048 * 1024 * 8192 * 8


def test_configuration_file_keeps_the_published_widths():
    """Every number of the catalog row's ``config`` under the same key;
    only the depth is cut."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "olmoe-1b-7b.json")) as f:
        cfg = json.load(f)
    published = {"attention_bias": False, "clip_qkv": None,
                 "hidden_act": "silu", "hidden_size": 2048,
                 "intermediate_size": 1024, "max_position_embeddings": 4096,
                 "model_type": "olmoe", "norm_topk_prob": False,
                 "num_attention_heads": 16, "num_experts": 64,
                 "num_experts_per_tok": 8, "num_hidden_layers": 16,
                 "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
                 "rope_scaling": None, "rope_theta": 10000,
                 "tie_word_embeddings": False, "vocab_size": 50304}
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"])

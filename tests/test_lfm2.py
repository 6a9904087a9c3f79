"""LFM2-8B-A1B on the dense path: the gated short convolution against the
plain reference that sits beside the benchmark's configuration (value,
four gradients, causality, zeros before a row's first position);
``models.Lfm2`` of every block kind through ``executor.make_train_step`` /
``Trainer`` against that reference; grouped-query attention through the
flash kernels (interpret mode) against einsum with repeated heads; the four
quarter shares of one expert layer adding up to the uncut layer; the
configuration file, the parameter counts and the FLOP counts by hand; the
benchmark's new readers on programs without their scopes."""

import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.executor import Trainer, make_train_step
from paddle_tpu.models import Lfm2, Lfm2Config, lfm2_loss
from paddle_tpu.models.lfm2 import LAYER_TYPES
from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.ops.short_conv import (causal_depthwise_conv,
                                       gated_short_conv)
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LR = 2.0 ** 16
_BIAS = "expert_bias"


def _load(name, *parts):
    path = os.path.join(ROOT, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("_lfm2_reference", "configs", "lfm2-8b-a1b.reference.py")
FLOPS = _load("_flops_lfm2", "harness", "flops_lfm2.py")

#: 4 query / 2 key-value heads of 8, 8 experts of 16, 2 a token
SMALL = dict(vocab_size=97, hidden_size=32, num_heads=4, num_kv_heads=2,
             dense_size=48, num_experts=8, experts_per_token=2,
             expert_size=16, max_seq_len=16, init_std=0.05)
#: the benchmark cell's five layers
SLICE = ("conv", "full_attention", "conv", "conv", "conv")


def _ref_cfg(cfg: Lfm2Config):
    """The model's sizes under the configuration file's keys."""
    return {"layer_types": list(cfg.layer_types), "first_layer": 0,
            "num_hidden_layers": cfg.num_layers,
            "num_dense_layers": cfg.num_dense_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "num_experts_per_tok": cfg.experts_per_token,
            "router_width": cfg.num_experts, "held_first": cfg.held[0],
            "num_experts": cfg.held[1],
            "routed_scaling_factor": cfg.routed_scale,
            "norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "bias_update_rate": cfg.bias_update_rate}


def _batch(cfg: Lfm2Config, batch: int, seed: int):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len + 1),
                        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def _random_biases(state, seed):
    """Biases as a trained model's: without them the rule "the bias moves
    the choice" is not exercised by the step."""
    rng = np.random.default_rng(seed)
    for name, b in state["buffers"].items():
        if name.endswith(_BIAS):
            state["buffers"][name] = jnp.asarray(
                rng.normal(size=b.shape) * 0.02, jnp.float32)
    return state


def _sgd_step(model, ids, labels, amp=False, seed=11):
    """The TRAIN STEP's loss, gradients (SGD: (before - after) / lr, lr a
    large power of two) and buffers after it."""
    state = _random_biases(
        jax.tree_util.tree_map(jnp.array, nn.get_state(model)), seed)
    opt = optimizer.SGD(learning_rate=LR)
    step = make_train_step(model, opt, lfm2_loss, donate=False, amp=amp)
    new_state, _, loss = step(state, opt.init(state["params"]),
                              jax.random.key(0), (jnp.asarray(ids),),
                              (jnp.asarray(labels),))
    grads = {k: (np.asarray(state["params"][k]) - np.asarray(v)) / LR
             for k, v in new_state["params"].items()}
    return float(loss), grads, new_state["buffers"], state


# -- the operator ----------------------------------------------------------

def _conv_operands(seed=0, B=3, L=9, C=5, K=3):
    r = np.random.default_rng(seed)
    b, g, x = (jnp.asarray(r.normal(size=(B, L, C)), jnp.float32)
               for _ in range(3))
    return b, g, x, jnp.asarray(r.normal(size=(C, K)), jnp.float32)


@pytest.mark.parametrize("what", ["value", "b", "g", "x", "w"])
def test_short_conv_matches_the_reference(what):
    """``ops.short_conv.gated_short_conv`` (shifted slices of one padded
    product) against the reference's explicit sum over three shifted
    copies: the value, and the gradient with respect to each of its four
    operands under a random cotangent."""
    args = _conv_operands()
    cot = jnp.asarray(np.random.default_rng(1).normal(size=args[0].shape),
                      jnp.float32)
    if what == "value":
        got, want = gated_short_conv(*args), REF.short_conv(*args)
        assert got.shape == args[0].shape
    else:
        i = "bgxw".index(what)
        got = jax.grad(lambda *a: jnp.sum(gated_short_conv(*a) * cot),
                       argnums=i)(*args)
        want = jax.grad(lambda *a: jnp.sum(REF.short_conv(*a) * cot),
                        argnums=i)(*args)
        assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_short_conv_by_hand():
    """c_t = w0 z_{t-2} + w1 z_{t-1} + w2 z_t, the current position under
    the LAST tap (torch's Conv1d with padding K-1, cut to L outputs)."""
    z = jnp.asarray([[[1.0], [10.0], [100.0], [1000.0]]])
    w = jnp.asarray([[3.0, 5.0, 7.0]])
    got = np.asarray(causal_depthwise_conv(z, w))[0, :, 0]
    np.testing.assert_array_equal(got, [7.0, 75.0, 753.0, 7530.0])


@pytest.mark.parametrize("t", [0, 1, 4, 7])
def test_short_conv_position_t_ignores_later_positions(t):
    b, g, x, w = _conv_operands(2)
    out = gated_short_conv(b, g, x, w)
    later = jnp.arange(b.shape[1])[None, :, None] > t
    noise = jnp.asarray(np.random.default_rng(3).normal(size=b.shape),
                        jnp.float32)
    moved = gated_short_conv(*(jnp.where(later, a + noise, a)
                               for a in (b, g, x)), w)
    np.testing.assert_array_equal(np.asarray(out[:, :t + 1]),
                                  np.asarray(moved[:, :t + 1]))
    assert not np.allclose(np.asarray(out[:, t + 1:]),
                           np.asarray(moved[:, t + 1:]))


def test_short_conv_first_positions_see_zeros_not_the_previous_row():
    """Each row of the batch is one sequence: positions 0 and 1 read zeros
    where a flattened [B*L] convolution would read the previous row's
    tail."""
    b, g, x, w = _conv_operands(4)
    out = np.asarray(gated_short_conv(b, g, x, w))
    z = np.asarray(b * x)
    np.testing.assert_allclose(out[:, 0], np.asarray(g)[:, 0]
                               * (np.asarray(w)[:, 2] * z[:, 0]), rtol=1e-6)
    np.testing.assert_allclose(
        out[:, 1], np.asarray(g)[:, 1] * (np.asarray(w)[:, 1] * z[:, 0]
                                          + np.asarray(w)[:, 2] * z[:, 1]),
        rtol=1e-6)
    # another row 0 leaves row 1 as it was
    other = gated_short_conv(b.at[0].add(1.0), g, x.at[0].add(1.0), w)
    np.testing.assert_array_equal(out[1:], np.asarray(other)[1:])


# -- the model against the reference ---------------------------------------

STACKS = {
    "conv_dense+conv_experts": (("conv", "conv"), 1, (2, 2)),
    "attn_dense+attn_experts": (("full_attention", "full_attention"), 1,
                                (2, 2)),
    "attn_experts+conv_experts": (("full_attention", "conv"), 0, (6, 2)),
    "slice_whole": (SLICE, 1, (0, 8)),
    "slice_held_2_3": (SLICE, 1, (2, 2)),
    "slice_held_4_7": (SLICE, 1, (4, 4)),
}


@pytest.mark.parametrize("stack", ["slice_whole", "slice_held_2_3",
                                   "slice_held_4_7"])
def test_rows_walked_is_stacked_a_layer_within_the_rung(stack):
    """``dispatch_rows_walked`` leaves the step beside ``dispatch_rung``:
    one count an expert layer, the whole chunks the bounded buffer's row
    movement passed over — never more than the rung, and the rung itself
    where every assignment is live."""
    kinds, dense, held = STACKS[stack]
    pt.seed(3)
    cfg = Lfm2Config(**SMALL, layer_types=kinds, num_dense_layers=dense,
                     held=held)
    ids, labels = _batch(cfg, 2, 5)
    _, _, buffers, _ = _sgd_step(Lfm2(cfg), ids, labels)
    walked = np.asarray(buffers["dispatch_rows_walked"])
    rung = np.asarray(buffers["dispatch_rung"])
    assert walked.shape == rung.shape == (cfg.expert_layers,)
    assert walked.dtype == np.int32 and (walked <= rung).all()
    landed = np.asarray(buffers["held_assignments"])
    if held == (0, cfg.num_experts):
        np.testing.assert_array_equal(walked, rung)
    else:       # a buffer this small is one chunk: walked whole, or not
        np.testing.assert_array_equal(walked, np.where(landed > 0, rung, 0))


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_train_step_matches_reference(stack):
    """The loss, EVERY gradient leaf and the router biases after the step,
    float32, against the reference at 1e-5, for every kind of block (either
    mixer before the dense feed-forward and before the experts) and for the
    benchmark cell's five layers at three held shares: the same function by
    another route."""
    kinds, dense, held = STACKS[stack]
    pt.seed(3)
    cfg = Lfm2Config(**SMALL, layer_types=kinds, num_dense_layers=dense,
                     held=held)
    model = Lfm2(cfg)
    ids, labels = _batch(cfg, 2, 5)
    loss, grads, buffers, state = _sgd_step(model, ids, labels)
    ref = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg),
                             buffers=state["buffers"])
    assert set(grads) == set(ref["grads"])
    assert abs(loss - ref["loss"]) <= 1e-5 * ref["loss"]
    for name, r in ref["grads"].items():
        top = np.max(np.abs(r))
        assert top > 0, name
        assert np.max(np.abs(grads[name] - r)) <= 1e-5 * top, name
    # counters: every assignment counted, those that landed here computed
    T, k = ids.size, cfg.experts_per_token
    counts = np.asarray(buffers["expert_counts"])
    assert counts.shape == (cfg.expert_layers, cfg.num_experts)
    assert (counts.sum(axis=1) == T * k).all()
    np.testing.assert_array_equal(counts, ref["counts"])
    first, n = held
    np.testing.assert_array_equal(np.asarray(buffers["held_assignments"]),
                                  counts[:, first:first + n].sum(axis=1))
    assert (np.asarray(buffers["held_assignments"])
            <= np.asarray(buffers["dispatch_rung"])).all()
    assert int(buffers["tokens_dropped"]) == 0
    # the bias moved against the load, by the rate, and nowhere else
    for i, name in enumerate(REF.bias_names(_ref_cfg(cfg))):
        np.testing.assert_array_equal(np.asarray(buffers[name]),
                                      ref["bias_after"][name])
        moved = np.asarray(buffers[name]) - np.asarray(state["buffers"][name])
        want = cfg.bias_update_rate * np.sign(counts[i].mean() - counts[i])
        np.testing.assert_allclose(moved, want, atol=1e-8)


def test_trainer_trains_and_updates_the_bias():
    """``Trainer`` with ``amp`` and AdamW, the cell's path: the loss falls,
    the biases leave zero, the counters are the last step's."""
    pt.seed(1)
    cfg = Lfm2Config(**SMALL, layer_types=SLICE, num_dense_layers=1,
                     held=(2, 2))
    trainer = Trainer(Lfm2(cfg), optimizer.AdamW(3e-3, weight_decay=0.1,
                                                 beta2=0.95),
                      lfm2_loss, amp=True)
    ids, labels = _batch(cfg, 4, 2)
    losses = [float(trainer.train_step(ids, labels)) for _ in range(12)]
    assert losses[-1] < losses[0] - 0.2 and np.isfinite(losses).all()
    b = trainer.state["buffers"]
    assert all(float(jnp.max(jnp.abs(b[f"blocks.{i}.moe.{_BIAS}"]))) > 0
               for i in range(1, 5))
    assert np.asarray(b["expert_counts"]).sum() == 4 * ids.size * 2
    assert int(b["tokens_dropped"]) == 0


def test_tied_head_gradient_is_the_sum_of_both_uses():
    """The embedding is read twice — as the table and, transposed, as the
    head: its gradient is the sum of both, and the model has no second
    vocabulary matrix."""
    pt.seed(2)
    cfg = Lfm2Config(**SMALL, layer_types=("conv",), num_dense_layers=0,
                     held=(0, 8))
    model = Lfm2(cfg)
    names = [n for n, _ in model.named_parameters()]
    assert "embed" in names and not any("head" in n for n in names)
    state = nn.get_state(model)
    ids, labels = (jnp.asarray(a) for a in _batch(cfg, 1, 0))

    def by_use(embed_in, embed_out):
        x = jnp.take(embed_in, ids, axis=0)
        for block in model.blocks:
            x, _ = block(x)
        return lfm2_loss(model.norm_f(x) @ embed_out.T, labels)

    embed = state["params"]["embed"]
    g_in, g_out = jax.grad(by_use, argnums=(0, 1))(embed, embed)
    whole = jax.grad(lambda p: lfm2_loss(nn.functional_call(
        model, {"params": p, "buffers": state["buffers"]}, ids,
        training=True)[0], labels))(state["params"])
    np.testing.assert_allclose(np.asarray(whole["embed"]),
                               np.asarray(g_in + g_out), atol=1e-7)
    assert float(jnp.max(jnp.abs(g_in))) > 0 < float(jnp.max(jnp.abs(g_out)))


# -- grouped-query attention ------------------------------------------------

def test_gqa_through_flash_matches_einsum_with_repeated_heads():
    """The kernel path the attention block takes on the chip — causal, head
    width 64, k and v repeated from 2 to 8 heads first — in interpret mode,
    float32 operands, against einsum attention over the repeated heads:
    the output and the gradients of q and of the UNREPEATED k and v (the
    repeat's backward is the sum over a group)."""
    r = np.random.default_rng(0)
    B, L, H, G, D = 1, 256, 8, 2, 64
    q = jnp.asarray(r.normal(size=(B, L, H, D)) * 0.3, jnp.float32)
    k = jnp.asarray(r.normal(size=(B, L, G, D)) * 0.3, jnp.float32)
    v, w = (jnp.asarray(r.normal(size=(B, L, n, D)), jnp.float32)
            for n in (G, H))
    rep = lambda a: jnp.repeat(a, H // G, axis=2)

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) / math.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), rep(v))

    flash = lambda q, k, v: flash_attention(
        q, rep(k), rep(v), causal=True, block_q=128, block_k=128,
        interpret=True, precision="highest")
    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(plain(q, k, v)), atol=2e-5)
    # query heads 0..3 read key-value head 0, heads 4..7 head 1
    only0 = plain(q, k.at[:, :, 1].set(0.0), v.at[:, :, 1].set(0.0))
    np.testing.assert_allclose(np.asarray(only0[:, :, :4]),
                               np.asarray(plain(q, k, v)[:, :, :4]),
                               atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_attention_layer_flash_and_einsum_agree():
    """The attention sublayer's two paths — the kernels (interpret mode,
    bf16 operands as on the chip) and the einsum — give the same output to
    bf16's rounding: the head norms, the rotary and the repeat are the
    kernel path's too."""
    pt.seed(4)
    cfg = Lfm2Config(**SMALL, layer_types=("full_attention",),
                     num_dense_layers=0, held=(0, 8))
    attn = Lfm2(cfg).blocks[0].attn
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 32)),
                    jnp.float32)
    cfg.attn_impl = "einsum"
    a = np.asarray(attn(x))
    cfg.attn_impl = "flash"
    b = np.asarray(attn(x))
    assert a.shape == (2, 16, 32) and np.max(np.abs(a)) > 0
    assert np.max(np.abs(a - b)) <= 0.02 * np.max(np.abs(a))


# -- the share of the experts ------------------------------------------------

@pytest.mark.parametrize("biased", [False, True], ids=["zero_bias",
                                                       "random_bias"])
def test_the_four_quarter_shares_add_up_to_the_uncut_layer(biased):
    """One expert layer at the published counts — 32 experts, 4 a token —
    cut as the deployment cuts it: ranks holding experts 0..7, 8..15,
    16..23, 24..31 each return their own experts' part; the four parts add
    up to what the reference gives for the uncut 32-expert layer (no shared
    expert to count once)."""
    r = np.random.default_rng(7)
    T, d, f, E, k = 64, 16, 12, 32, 4
    x = jnp.asarray(r.normal(size=(T, d)), jnp.float32)
    p = {"moe.router_w": jnp.asarray(r.normal(size=(d, E)) * 0.3,
                                     jnp.float32)}
    for name, shape in (("w_gate", (E, d, f)), ("w_up", (E, d, f)),
                        ("w_down", (E, f, d))):
        p["moe." + name] = jnp.asarray(r.normal(size=shape) * 0.3,
                                       jnp.float32)
    bias = jnp.asarray(r.normal(size=(E,)) * 0.05 * biased, jnp.float32)
    whole, _, index, _, counts = REF.experts(
        p, "moe.", x, bias, {"num_experts_per_tok": k, "held_first": 0,
                             "num_experts": E, "routed_scaling_factor": 1.0},
        None, lambda a: a)
    total = jnp.zeros_like(x)
    landed = 0
    for first in (0, 8, 16, 24):
        part, route = moe.held_moe(
            x, p["moe.router_w"], bias, p["moe.w_gate"][first:first + 8],
            p["moe.w_up"][first:first + 8], p["moe.w_down"][first:first + 8],
            k, (first, 8), 1.0)
        np.testing.assert_array_equal(np.sort(route["index"], axis=1),
                                      np.sort(index, axis=1))
        assert int(route["dropped"]) == 0
        landed += int(route["held_assignments"])
        total = total + part
        # a rank's part is what the reference gives for the same share
        share, *_ = REF.experts(
            {key: (v[first:first + 8] if v.ndim == 3 else v)
             for key, v in p.items()}, "moe.", x, bias,
            {"num_experts_per_tok": k, "held_first": first, "num_experts": 8,
             "routed_scaling_factor": 1.0}, None, lambda a: a)
        np.testing.assert_allclose(np.asarray(part), np.asarray(share),
                                   atol=2e-5)
    assert landed == T * k == int(jnp.sum(counts))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)
    assert float(jnp.max(jnp.abs(whole))) > 0.1


def test_a_quarter_held_layer_runs_both_forms():
    """At a quarter held the buffer is 2 T rows for T even-load
    assignments; a bias that sends every choice to the held experts (4 T)
    takes the every-expert form, and both give the reference's part."""
    r = np.random.default_rng(9)
    T, d, f, E, k = 256, 16, 12, 32, 4
    assert moe.dispatch_ladder(T, k, E, 8) == (2 * T, 8 * T)
    x = jnp.asarray(r.normal(size=(T, d)), jnp.float32)
    router = jnp.asarray(r.normal(size=(d, E)) * 0.3, jnp.float32)
    banks = [jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
             for s in ((8, d, f), (8, d, f), (8, f, d))]
    p = {"m.router_w": router, "m.w_gate": banks[0], "m.w_up": banks[1],
         "m.w_down": banks[2]}
    cfg = {"num_experts_per_tok": k, "held_first": 8, "num_experts": 8,
           "routed_scaling_factor": 1.0}
    for push, rung in ((0.0, 2 * T), (1.0, 8 * T)):
        bias = jnp.zeros((E,)).at[8:16].add(push)
        part, route = moe.held_moe(x, router, bias, *banks, k, (8, 8), 1.0)
        assert int(route["rung"]) == rung and int(route["dropped"]) == 0
        want, *_ = REF.experts(p, "m.", x, bias, cfg, None, lambda a: a)
        np.testing.assert_allclose(np.asarray(part), np.asarray(want),
                                   atol=5e-5)
    assert int(route["held_assignments"]) == T * k


# -- what the model refuses, its sizes, its configuration -------------------

@pytest.mark.parametrize("bad", [
    dict(layer_types=("conv", "sliding_attention")),
    dict(layer_types=("conv",), num_dense_layers=1),
    dict(num_heads=4, num_kv_heads=3),
    dict(held=(6, 4)),
    dict(experts_per_token=9),
], ids=["unknown_mixer", "no_expert_layer", "kv_heads_do_not_divide",
        "held_outside", "more_a_token_than_experts"])
def test_what_the_model_cannot_run_is_refused(bad):
    with pytest.raises(EnforceNotMet):
        Lfm2(Lfm2Config(**{**SMALL, "layer_types": SLICE, "held": (0, 8),
                           **bad}))


def test_residual_init_scales_the_projections_into_the_stream():
    pt.seed(5)
    cfg = Lfm2Config(**dict(SMALL, init_std=0.5), layer_types=SLICE,
                     num_dense_layers=1, held=(0, 8), total_layers=1250)
    assert cfg.out_std == 0.01
    for name, p in Lfm2(cfg).named_parameters():
        if name.endswith("weight"):
            continue                      # norms: ones
        if name.endswith("w_conv"):       # torch's Conv1d default
            assert float(jnp.max(jnp.abs(p))) <= 1 / math.sqrt(3)
            continue
        want = 0.01 if name.endswith(("w_out", "wo", "w_down")) else 0.5
        assert abs(float(jnp.std(p)) - want) < 0.15 * want, name


@pytest.mark.parametrize("which", ["cut", "whole", "allocated"])
def test_parameter_counts(which):
    """``Lfm2Config.parameter_count`` from the shapes alone: the
    benchmark's cut (ISSUE 33's table: about 508 M), the whole published
    model (8.34 B with the tied head: the published 8.3B), and a small
    model's allocated leaves."""
    if which == "cut":
        cfg = Lfm2Config(vocab_size=16384, layer_types=LAYER_TYPES[1:6],
                         num_dense_layers=1, held=(0, 8))
        assert cfg.layer_types == SLICE
        conv = 2048 * 6144 + 2048 * 2048 + 2048 * 3
        attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
        layer = 8 * 3 * 2048 * 1792 + 2048 * 32
        want = (4 * conv + attn + 3 * 2048 * 7168 + 4 * layer
                + 16384 * 2048 + 11 * 2048)
        assert cfg.parameter_count() == want == 507_820_160
    elif which == "whole":
        cfg = Lfm2Config()
        assert cfg.layer_types.count("conv") == 18 and cfg.num_layers == 24
        assert cfg.parameter_count() == 8_339_929_856
    else:
        cfg = Lfm2Config(**SMALL, layer_types=SLICE, num_dense_layers=1,
                         held=(2, 2))
        leaves = nn.get_state(Lfm2(cfg))["params"].values()
        assert cfg.parameter_count() == sum(int(np.prod(v.shape))
                                            for v in leaves)


def test_configuration_file_keeps_the_published_widths():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 7168, "layer_types": list(LAYER_TYPES),
                 "max_position_embeddings": 128000,
                 "moe_intermediate_size": 1792, "norm_eps": 1e-5,
                 "norm_topk_prob": True, "num_attention_heads": 32,
                 "num_experts_per_tok": 4, "num_key_value_heads": 8,
                 "rope_theta": 1000000, "routed_scaling_factor": 1,
                 "use_expert_bias": True}
    for key, want in published.items():
        assert cfg[key] == want, key
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                   "num_experts", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 24,
                                "num_dense_layers": 2, "num_experts": 32,
                                "vocab_size": 65536}
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"],
            cfg["num_experts"], cfg["vocab_size"]) == (5, 1, 8, 16384)
    assert cfg["router_width"] == 32 and cfg["held_first"] == 0
    # published layers 1..5: one whole period after the dense layer
    assert REF.layer_kinds(cfg) == list(SLICE) == FLOPS.layer_kinds(cfg)
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    adapter = _load("_lfm2_adapter", "adapters", "causal_conv_moe_lm.py")
    model_cfg = adapter._model_cfg(cfg)
    assert model_cfg.layer_types == SLICE and model_cfg.held == (0, 8)
    assert model_cfg.parameter_count() == 507_820_160
    assert model_cfg.total_layers == 24 and model_cfg.head_dim == 64
    for key in ("deployment", "parameters", "distortion", "departures",
                "assumed", "rehearsal"):
        assert cfg[key], key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["lfm2-8b-a1b"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    cells = [w for w in bench["workloads"] if w["config"] == "lfm2-8b-a1b"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        ("lfm2_8b_a1b_seq4096", "lm_zipf_seq4096", 1)]


def test_benchmark_flop_counts_by_hand():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    # one conv operator: W_in 2048 x 6144 and W_out 2048 x 2048
    assert FLOPS.conv_operator_flops_per_token(cfg) \
        == 2 * (2048 * 6144 + 2048 * 2048) == 33_554_432
    assert FLOPS.conv_blocks(cfg) == 4
    # W_q, W_o 2048 x 2048; W_k, W_v 2048 x 512; 32 heads x (64 + 64) x
    # 2 x 4097 / 2 causal positions
    attn = 2 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 32 * 128 * 4097
    assert FLOPS.attention_flops_per_token(cfg, 4096) == attn == 37_752_832
    expert = 3 * 2 * 2048 * 1792
    assert FLOPS.swiglu_flops(2048, 1792) == expert == 22_020_096
    assert FLOPS.held_share(cfg) == 0.25
    forward = (4 * 33_554_432 + attn + 3 * 2 * 2048 * 7168
               + 4 * (2 * 2048 * 32 + 4 * 0.25 * expert)
               + 2 * 2048 * 16384)
    assert forward == 415_764_480
    assert FLOPS.train_flops_per_token(cfg, 4096) == 3 * forward \
        == 1_247_293_440
    assert FLOPS.held_expert_flops(cfg, 8192) == 3 * 8192 * expert
    assert FLOPS.conv_operator_flops(cfg, 8192) \
        == 3 * 8192 * 4 * 33_554_432
    # the conv mixers and the held experts: 53% of the required FLOPs
    share = (4 * 33_554_432 + 4 * expert) / forward
    assert 0.53 < share < 0.54


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
def test_benchmark_flash_floor_by_hand(kernel):
    """One causal call on the cell's 4 x 4096 tokens: 32 query heads of
    64 over 4096 x 4097 / 2 pairs; q, dO, o, dq at 32 heads, k, v, dk, dv
    at 8; bf16 operands, float32 results, lse (and delta) a query row."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    got = FLOPS.flash_kernel_floor(kernel, cfg, 4, 4096, peaks)
    one = 2 * 64 * 4 * 32 * 4096 * 4097 // 2        # one matmul
    q, kv = 4 * 32 * 4096 * 64, 4 * 8 * 4096 * 64   # elements
    rows = 4 * 32 * 4096
    flop, moved = {
        "flash_fwd": (2 * one, 2 * (q + 2 * kv) + 4 * q + 4 * rows),
        "flash_bwd_dq": (3 * one, 2 * (2 * q + 2 * kv) + 8 * rows + 4 * q),
        "flash_bwd_dkv": (4 * one,
                          2 * (2 * q + 2 * kv) + 8 * rows + 4 * 2 * kv),
    }[kernel]
    assert one == 137_472_507_904
    assert (got["flop"], got["bytes"]) == (flop, moved)
    # FLOP-bound at every one of the three: 1.40, 2.09, 2.79 ms
    assert got["floor_s"] == flop / 197e12 > moved / 819e9


# -- the cell's ``correct`` against planted faults ---------------------------

@pytest.mark.parametrize("fault", ["none", "half_the_batch_left_out"])
def test_check_sees_a_step_that_trains_on_part_of_the_batch(fault):
    """The adapter's check at the rehearsal's sizes: sound, it is correct;
    with a loss that leaves half of the batch out (labels -1, "no
    position") the one-sequence float32 comparison cannot tell, and the
    step as measured, on a batch of distinct sequences, is not correct."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import spec

    cell = spec.Cell(spec.load_benchmark(), "lfm2_8b_a1b_seq4096",
                     rehearse=True)
    system = cell.adapter().build(cell, 7, jax.devices()[:1], True,
                                  cell.generator(), {})
    ids, _ = system.check_items
    assert ids.shape[0] == system.batch > 1
    assert len({row.tobytes() for row in ids}) == len(ids)   # distinct
    if fault != "none":
        def partial_loss(logits, labels):
            return lfm2_loss(logits,
                             labels.at[:labels.shape[0] // 2].set(-1))

        system.loss_fn = partial_loss
    out = system.check_reference(cell.reference())
    assert out["f32"]["ok"] and out["update"]["ok"] and out["forms"]["ok"]
    assert out["amp"]["ok"] == out["ok"] == (fault == "none")
    if fault != "none":
        assert out["amp"]["loss_rel"] > 10 * out["amp"]["tol"]["loss_rel"] \
            or out["amp"]["grad_leaf_rel"] > 0.5


# -- the benchmark's new readers on programs without their scopes -----------

NEW_METRICS = ["sconv_model_flops_utilization", "sconv_block_share",
               "sconv_mix_share", "sconv_operator_mxu_share",
               "gqa_repeat_share", "moe_quarter_held_expert_mxu_share",
               "gqa_flash_fwd_roofline", "gqa_flash_bwd_dq_roofline",
               "gqa_flash_bwd_dkv_roofline"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_reads_none_never_zero_without_its_scope(metric):
    """On the parent's program (no ``pt.conv`` / ``pt.gqa`` scope, another
    configuration, or no trace at all) each new reader returns None and
    does not raise: the recorded DeepFM trace stands for such a program."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import trace

    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "scoped_trace.json")) as f:
        recorded = json.load(f)
    read = _load("_metric_" + metric, "metrics", metric + ".py").read

    class System:
        unit, seq, units_per_dispatch = "tokens", 4096, 8192
        held_assignments_per_dispatch = 8192.0

        def compiled_text(self):
            return recorded["hlo_text"]

    class Cell:
        config = {"qk_rope_head_dim": 64}      # another configuration's

    ctx = {"trace": trace.reduce_trace(recorded["events"]), "hlo_text": "",
           "system": System(), "cell": Cell(), "rehearse": False,
           "chips": 1, "rate_per_chip": 5e4, "device_kind": "TPU v5 lite",
           "window": {"dispatches": 3}}
    assert read(ctx) is None
    assert read(dict(ctx, trace=None, _scope_shares=None)) is None

"""SmallThinker-21BA3B on the dense path: ``models.SmallThinker`` (windowed
rotary layers beside global position-free ones, the router on the layer's
input, ReLU experts of which a share is held) through
``executor.make_train_step`` / ``Trainer`` against the plain reference that
sits beside the benchmark's configuration — loss, every gradient leaf, the
routers' logits, AdamW's first step; the eight shares of one expert layer
adding up to the uncut layer, with the route made from another tensor than
the rows dispatched; both forms of an eighth-held layer; the five planted
faults the cell's ``correct`` must refuse, refused by the reference's
``compare*`` at a small size; the configuration file, the parameter counts
and the FLOP counts by hand; the benchmark's new readers on programs
without their scopes."""

import importlib.util
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import amp, nn, optimizer
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.executor import Trainer, make_train_step
from paddle_tpu.models import (SmallThinker, SmallThinkerConfig,
                               smallthinker_loss)
from paddle_tpu.models.transformer import _banded_attention, repeat_kv
from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "smallthinker-21b-a3b.json")


def _load(name, *parts):
    path = os.path.join(ROOT, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("_smallthinker_reference", "configs",
            "smallthinker-21b-a3b.reference.py")
FLOPS = _load("_flops_swa", "harness", "flops_swa.py")

#: 4 query / 2 key-value heads of 8, a window of 6 keys under 24
#: positions, 8 experts of 16 of which two are held, 2 a token
SMALL = dict(vocab_size=97, hidden_size=32, num_heads=4, num_kv_heads=2,
             head_dim=8, sliding_window_size=6, num_layers=4,
             router_width=8, experts_per_token=2, expert_size=16,
             held=(2, 2), max_seq_len=24, init_std=0.08, total_layers=52)
STACKS = {
    "published_period": {},                       # layers 0..3: 0 1 1 1
    "windowed_alone": dict(first_layer=1, num_layers=3),
    "whole_layer_held": dict(held=(0, 8)),
    "window_as_long_as_the_sequence": dict(sliding_window_size=24),
}


def _ref_cfg(cfg: SmallThinkerConfig):
    """The model's sizes under the configuration file's keys."""
    return {"sliding_window_layout": list(cfg.sliding_window_layout),
            "rope_layout": list(cfg.rope_layout),
            "first_layer": cfg.first_layer,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "sliding_window_size": cfg.sliding_window_size,
            "moe_num_active_primary_experts": cfg.experts_per_token,
            "router_width": cfg.router_width, "held_first": cfg.held[0],
            "moe_num_primary_experts": cfg.held[1],
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "tie_word_embeddings": False, "norm_topk_prob": True,
            "moe_primary_router_apply_softmax": True, "rope_scaling": None}


def _batch(cfg: SmallThinkerConfig, batch: int, seed: int):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq_len + 1),
                        dtype=np.int32)
    return toks[:, :-1], toks[:, 1:]


def _model(seed=0, **over):
    pt.seed(seed)
    cfg = SmallThinkerConfig(**{**SMALL, **over})
    return SmallThinker(cfg), cfg


def _f32_function(model, ids, labels, use_amp=False):
    """Loss, gradients and routing of the float32 function (or, with
    ``use_amp``, of the same call under ``amp``), as the cell's check
    takes them."""
    state = nn.get_state(model)

    def loss_of(params):
        with amp.step_ctx(use_amp):
            (logits, routes), _ = nn.functional_call(
                model, {"params": params, "buffers": state["buffers"]},
                jnp.asarray(ids), output_routing=True, training=True)
        return smallthinker_loss(logits, jnp.asarray(labels)), routes

    with jax.default_matmul_precision("highest"):
        (loss, routes), grads = jax.jit(jax.value_and_grad(
            loss_of, has_aux=True))(state["params"])
    return {"loss": float(loss), "grads": grads,
            "router_logits": np.asarray(routes["logits"], np.float64),
            "expert_index": np.asarray(routes["index"])}, state["params"]


def _judged(got, params, ids, labels, cfg):
    """(routing verdict, loss-and-gradient verdict) of the reference's own
    ``compare*`` in ``f32`` mode, its near-tie rule included."""
    ref = REF.loss_and_grads(params, ids, labels, _ref_cfg(cfg))
    routing = REF.compare_routing(got, ref, "f32")
    if routing["near_ties_resolved_differently"]:
        ref = REF.loss_and_grads(params, ids, labels, _ref_cfg(cfg),
                                 expert_index=got["expert_index"])
    return routing, REF.compare(got, ref, "f32")


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("stack", sorted(STACKS))
def test_float32_function_matches_reference(stack):
    """Loss, every gradient leaf, the routers' logits and the chosen
    experts against the reference written from the equations — the router
    on the layer's INPUT, the band, the position-free layer, ReLU."""
    model, cfg = _model(**STACKS[stack])
    ids, labels = _batch(cfg, 2, 3)
    got, params = _f32_function(model, ids, labels)
    routing, verdict = _judged(got, params, ids, labels, cfg)
    assert routing["ok"], routing
    assert routing["logit_abs"] <= 1e-7
    assert verdict["leaves_compared"] == verdict["leaves"] == len(params)
    assert verdict["grad_leaf_rel"] <= 2e-5, verdict["worst_leaves"]
    assert verdict["grad_leaf_l2"] <= 2e-5, verdict["worst_leaf_l2"]
    assert verdict["loss_rel"] <= 1e-6
    kinds = cfg.layer_kinds
    assert REF.layer_kinds(_ref_cfg(cfg)) == kinds
    assert FLOPS.layer_windows(_ref_cfg(cfg)) == [w for w, _ in kinds]


def test_adamw_step_matches_reference():
    """``make_train_step`` with AdamW from zero moments: the gradient read
    out of the first moment is the reference's, and parameters and second
    moments are ``adamw_first_step``'s."""
    model, cfg = _model()
    ids, labels = _batch(cfg, 2, 5)
    hyper = {"lr": 4e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1}
    opt = optimizer.AdamW(learning_rate=hyper["lr"], weight_decay=0.1,
                          beta1=0.9, beta2=0.95, epsilon=1e-8)
    step = make_train_step(model, opt, smallthinker_loss, donate=False)
    state = jax.tree_util.tree_map(jnp.array, nn.get_state(model))
    with jax.default_matmul_precision("highest"):
        new_state, new_opt, loss = step(
            state, opt.init(state["params"]), jax.random.key(0),
            (jnp.asarray(ids),), (jnp.asarray(labels),))
    slots = new_opt["slots"]
    update = REF.compare_update(state["params"], new_state["params"],
                                slots["m"], slots["v"], hyper)
    assert update["ok"] and update["leaves"] == len(state["params"]), update
    ref = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg))
    got = {"loss": float(loss),
           "grads": {k: v / 0.1 for k, v in slots["m"].items()}}
    verdict = REF.compare(got, ref, "f32")
    assert verdict["grad_leaf_rel"] <= 2e-5 and verdict["loss_rel"] <= 1e-6
    # a halved rate is seen
    assert not REF.compare_update(
        state["params"], new_state["params"], slots["m"], slots["v"],
        dict(hyper, lr=2e-4))["ok"]


def test_trainer_trains_under_amp_and_fills_the_counters():
    model, cfg = _model()
    tr = Trainer(model, optimizer.AdamW(learning_rate=3e-3),
                 smallthinker_loss, amp=True)
    ids, labels = _batch(cfg, 4, 1)
    losses = [float(tr.train_step(ids, labels)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.1
    b = {k: np.asarray(v) for k, v in tr.state["buffers"].items()}
    T, k = ids.size, cfg.experts_per_token
    assert b["expert_counts"].shape == (4, 8)
    assert (b["expert_counts"].sum(axis=1) == T * k).all()
    assert (b["held_assignments"] == b["expert_counts"][:, 2:4].sum(1)).all()
    assert (b["held_assignments"] <= b["dispatch_rung"]).all()
    assert (b["dispatch_rows_walked"] <= b["dispatch_rung"]).all()
    assert int(b["tokens_dropped"]) == 0


@pytest.mark.parametrize("recompute", ["experts", "blocks"])
def test_recompute_changes_no_value(recompute):
    model, cfg = _model()
    ids, labels = _batch(cfg, 2, 9)
    plain, _ = _f32_function(model, ids, labels)
    model.cfg.recompute = recompute
    again, _ = _f32_function(model, ids, labels)
    assert again["loss"] == pytest.approx(plain["loss"], rel=1e-6)
    for k, g in plain["grads"].items():
        np.testing.assert_allclose(np.asarray(again["grads"][k]),
                                   np.asarray(g), atol=1e-7, err_msg=k)


@pytest.mark.parametrize("window", [None, 5, 16, 40])
def test_attention_layer_flash_and_einsum_agree(window):
    """The model's einsum attention (blocks of queries, groups of heads)
    against the flash kernels (interpret mode) under the same mask, 4
    query heads on 2 key-value heads repeated first."""
    r = np.random.default_rng(2)
    q = jnp.asarray(r.normal(size=(2, 32, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(r.normal(size=(2, 32, 2, 8)), jnp.float32)
            for _ in range(2))
    k, v = repeat_kv(k, v, 4)
    want = _banded_attention(q, k, v, window)
    got = flash_attention(q, k, v, causal=True, window=window, block_q=16,
                          block_k=16, precision="highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    # and against the reference's own attention, k and v NOT repeated
    ref = REF.attention(q, k[:, :, ::2], v[:, :, ::2], window)
    np.testing.assert_allclose(np.asarray(want), np.asarray(ref), atol=2e-6)


# -- the share of the experts ------------------------------------------------

def _expert_layer(seed, T, d, f, E):
    r = np.random.default_rng(seed)
    x_in = jnp.asarray(r.normal(size=(T, d)), jnp.float32)   # router reads
    u = jnp.asarray(r.normal(size=(T, d)), jnp.float32)      # experts read
    p = {"moe.router_w": jnp.asarray(r.normal(size=(d, E)) * 0.3,
                                     jnp.float32)}
    for name, shape in (("w_gate", (E, d, f)), ("w_up", (E, d, f)),
                        ("w_down", (E, f, d))):
        p["moe." + name] = jnp.asarray(r.normal(size=shape) * 0.3,
                                       jnp.float32)
    return x_in, u, p


def _route(x_in, router_w, k):
    logits = moe.router_logits(x_in, router_w)
    route = moe.topk_route(logits, k, renormalise=True)
    route["logits"] = logits
    return route


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer at the published counts — 64 experts, 6 a token —
    cut as the deployment cuts it: ranks holding experts 0..7, 8..15, …
    each return their own experts' part of the route made from ANOTHER
    tensor than the rows they dispatch; the eight parts add up to what the
    reference gives for the uncut 64-expert layer."""
    T, d, f, E, k = 64, 16, 12, 64, 6
    x_in, u, p = _expert_layer(7, T, d, f, E)
    keys = lambda first, count: {
        "moe_num_active_primary_experts": k, "held_first": first,
        "moe_num_primary_experts": count}
    whole, _, index, _, counts = REF.experts(p, "moe.", x_in, u,
                                             keys(0, E), None, lambda a: a)
    route = _route(x_in, p["moe.router_w"], k)
    np.testing.assert_array_equal(np.sort(route["index"], axis=1),
                                  np.sort(index, axis=1))
    np.testing.assert_allclose(np.asarray(route["weight"]).sum(axis=1), 1.0,
                               atol=1e-6)
    total, landed = jnp.zeros_like(u), 0
    for first in range(0, E, 8):
        banks = [p["moe." + n][first:first + 8]
                 for n in ("w_gate", "w_up", "w_down")]
        part, got = moe.held_moe(u, None, None, *banks, k, (first, 8),
                                 route=route, activation=jax.nn.relu)
        assert int(got["dropped"]) == 0
        landed += int(got["held_assignments"])
        total = total + part
        # a rank's part is what the reference gives for the same share
        share, *_ = REF.experts(
            {key: (v[first:first + 8] if v.ndim == 3 else v)
             for key, v in p.items()}, "moe.", x_in, u, keys(first, 8),
            None, lambda a: a)
        np.testing.assert_allclose(np.asarray(part), np.asarray(share),
                                   atol=2e-5)
    assert landed == T * k == int(jnp.sum(counts))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=5e-5)
    assert float(jnp.max(jnp.abs(whole))) > 0.1


def test_an_eighth_held_layer_runs_both_forms():
    """At an eighth held the buffer is 1.5 T rows for 0.75 T even-load
    assignments; a router whose held columns are 16 times as wide (the
    cell's ``CHECK_ROUTER_PAST_THE_BUFFER``) sends about four of a token's
    six choices here and takes the every-expert form; both give the
    reference's part, value and gradients."""
    T, d, f, E, k = 512, 16, 12, 64, 6
    x_in, u, p = _expert_layer(9, T, d, f, E)
    banks = [p["moe." + n][:8] for n in ("w_gate", "w_up", "w_down")]
    held = {key: (v[:8] if v.ndim == 3 else v) for key, v in p.items()}
    keys = {"moe_num_active_primary_experts": k, "held_first": 0,
            "moe_num_primary_experts": 8}
    rungs = moe.dispatch_ladder(T, k, E, 8)
    assert rungs == (768, 4096)
    for scale, rung in ((1.0, rungs[0]), (16.0, rungs[1])):
        router = p["moe.router_w"].at[:, :8].multiply(scale)

        def part(u, router, *banks):
            out, route = moe.held_moe(
                u, None, None, *banks, k, (0, 8),
                route=_route(x_in, router, k), activation=jax.nn.relu)
            return jnp.sum(out ** 2), route

        def want(u, router, *banks):
            q = dict(held, **{"moe.router_w": router},
                     **dict(zip(("moe.w_gate", "moe.w_up", "moe.w_down"),
                                banks)))
            return jnp.sum(REF.experts(q, "moe.", x_in, u, keys, None,
                                       lambda a: a)[0] ** 2)

        (got, route), grads = jax.value_and_grad(
            part, argnums=(0, 1, 2, 3, 4), has_aux=True)(u, router, *banks)
        ref, ref_grads = jax.value_and_grad(
            want, argnums=(0, 1, 2, 3, 4))(u, router, *banks)
        assert int(route["rung"]) == rung and int(route["dropped"]) == 0
        np.testing.assert_allclose(float(got), float(ref), rtol=2e-5)
        for g, r in zip(grads, ref_grads):
            scale_ = float(jnp.max(jnp.abs(r)))
            assert scale_ > 0
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       atol=2e-5 * scale_)


def test_topk_route_renormalised_is_the_softmax_over_the_chosen():
    r = np.random.default_rng(4)
    logits = jnp.asarray(r.normal(size=(32, 16)), jnp.float32)
    plain = moe.topk_route(logits, 3)
    renorm = moe.topk_route(logits, 3, renormalise=True)
    np.testing.assert_array_equal(plain["index"], renorm["index"])
    chosen = jnp.take_along_axis(logits, renorm["index"], axis=1)
    np.testing.assert_allclose(np.asarray(renorm["weight"]),
                               np.asarray(jax.nn.softmax(chosen, axis=1)),
                               atol=1e-6)
    assert float(jnp.max(plain["weight"].sum(axis=1))) < 1.0
    g, *_ = REF.route(logits, 3)
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(g, renorm["index"], axis=1)),
        np.asarray(renorm["weight"]), atol=1e-6)


# -- the five planted faults of the cell's ``correct`` -----------------------

#: plants them in a model: the builder's tool that reads them on the chip
CONTROL = _load("_swa_fault_control", "tests", "swa_fault_control.py")


@pytest.mark.parametrize("fault", CONTROL.FAULTS)
def test_planted_fault_is_refused_by_the_reference(fault):
    """Each of the wrong programs the cell's ``correct`` must refuse
    (ISSUE 44, Tentpole 5), planted in the model at a small size and
    judged by the reference's own ``compare_routing`` / ``compare`` in
    ``f32`` mode: each fails a limit other than the loss's (the loss of a
    mean over few tokens is the weakest witness), the sound program
    none."""
    model, cfg = _model()
    ids, labels = _batch(cfg, 2, 13)
    published = _ref_cfg(cfg)
    with CONTROL.planted(types.SimpleNamespace(model=model), fault):
        got, params = _f32_function(
            model, ids, labels,
            use_amp=fault == "bf16_where_the_file_says_float32")
    ref = REF.loss_and_grads(params, ids, labels, published)
    routing = REF.compare_routing(got, ref, "f32")
    # the gradients against the reference GIVEN the program's own choice of
    # experts: a fault must show there too, not as a flipped choice alone
    ref = REF.loss_and_grads(params, ids, labels, published,
                             expert_index=got["expert_index"])
    verdict = REF.compare(got, ref, "f32")
    tol = REF.TOL["f32"]
    if fault == "none":
        assert routing["ok"] and verdict["grad_leaf_rel"] <= 2e-5
        return
    assert not (routing["ok"] and verdict["ok"])
    if fault == "router_fed_the_post_attention_stream":
        assert routing["logit_abs"] > 100 * tol["logit_abs"]
    else:
        # a leaf's norm refuses it — at this size bf16 reads 5.9e-3 there
        # and 7.5e-3 at a leaf's widest entry, under that limit — and, in
        # the four wrong programs, so does the widest entry
        assert verdict["grad_leaf_l2"] > 5 * tol["grad_leaf_l2"], verdict
        if fault != "bf16_where_the_file_says_float32":
            assert verdict["grad_leaf_rel"] > 10 * tol["grad_leaf_rel"]


# -- the cell's set-up -------------------------------------------------------

def test_set_up_moves_the_routers_alone():
    """``adapters/causal_swa_moe_lm._balance_router`` (this router has no
    bias to balance): gradient steps of the routers' load-balance term on
    the ROUTERS' weights — every other parameter is bit for bit what the
    seed made it, and the buffers are untouched."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import spec

    cell = spec.Cell(spec.load_benchmark(), "smallthinker_21b_seq16384",
                     rehearse=True)
    adapter = cell.adapter()
    system = adapter.build(cell, 7, jax.devices()[:1], True,
                           cell.generator(), {})
    assert system.balance["steps"] == adapter.ROUTER_STEPS
    pt.seed(7)
    fresh = dict(SmallThinker(adapter._model_cfg(cell.config))
                 .named_parameters())
    got = system.trainer.state["params"]
    assert set(got) == set(fresh)
    moved = {k for k in got
             if not np.array_equal(np.asarray(got[k]), np.asarray(fresh[k]))}
    assert moved == {f"blocks.{i}.moe.router_w" for i in range(4)}
    for k in moved:        # steps of a rate of 0.01: small beside a weight
        assert float(jnp.max(jnp.abs(got[k] - fresh[k]))) < 0.05
    assert int(system.trainer.global_step) == 0


def test_check_hands_the_trainer_back_whole():
    """``check_reference`` multiplies the first router's held columns for
    its own two programs and gives the step as measured the trainer's
    arrays (donated): afterwards the trainer holds what it held — every
    parameter bit for bit, the router its own again, the same kind of
    mapping — and can still lower its step (a traced run reads the
    compiled text AFTER the check)."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import spec

    cell = spec.Cell(spec.load_benchmark(), "smallthinker_21b_seq16384",
                     rehearse=True)
    system = cell.adapter().build(cell, 11, jax.devices()[:1], True,
                                  cell.generator(), {})
    tr = system.trainer
    before = {k: np.asarray(v) for k, v in tr.state["params"].items()}
    kind = type(tr.state["params"])
    out = system.check_reference(cell.reference())
    assert out["ok"], {k: v.get("ok") for k, v in out.items()
                       if isinstance(v, dict)}
    assert out["forms"]["dropped"] == [0, 0]
    assert type(tr.state["params"]) is kind
    assert list(tr.state["params"]) == list(before)
    for k, v in before.items():
        np.testing.assert_array_equal(np.asarray(tr.state["params"][k]), v,
                                      err_msg=k)
    assert "pt.attn.window" in tr.compiled_text(*system.host_items[0])


# -- what the model refuses, and its sizes -----------------------------------

@pytest.mark.parametrize("bad", [
    dict(num_heads=3), dict(held=(6, 4)), dict(experts_per_token=9),
    dict(first_layer=50, num_layers=4), dict(sliding_window_size=0),
    dict(recompute="all")])
def test_what_the_model_cannot_run_is_refused(bad):
    with pytest.raises(EnforceNotMet):
        SmallThinker(SmallThinkerConfig(**{**SMALL, **bad}))


def test_residual_init_scales_the_projections_into_the_stream():
    model, cfg = _model(hidden_size=64, expert_size=64)
    p = dict(model.named_parameters())
    want = 0.08 / np.sqrt(2 * 52)
    assert cfg.out_std == pytest.approx(want)
    for name in ("blocks.0.attn.wo", "blocks.3.moe.w_down"):
        assert float(jnp.std(p[name])) == pytest.approx(want, rel=0.1), name
    for name in ("blocks.0.attn.wq", "blocks.3.moe.w_gate", "embed", "head"):
        assert float(jnp.std(p[name])) == pytest.approx(0.08, rel=0.1), name


@pytest.mark.parametrize("which", ["cut", "whole", "allocated"])
def test_parameter_counts(which):
    if which == "cut":
        cfg = SmallThinkerConfig(vocab_size=18992, num_layers=4, held=(0, 8))
        layer = 20_971_520 + 163_840 + 8 * 5_898_240 + 5_120
        assert layer == 68_326_400
        assert cfg.parameter_count() == 4 * layer + 2 * 48_619_520 + 2_560 \
            == 370_547_200
    elif which == "whole":
        assert SmallThinkerConfig().parameter_count() \
            == 52 * 398_627_840 + 2 * 388_956_160 + 2_560 \
            == 21_506_562_560
    else:
        for over in ({}, dict(held=(0, 8)), dict(first_layer=2,
                                                 num_layers=2)):
            model, cfg = _model(**over)
            assert cfg.parameter_count() == sum(
                int(np.prod(v.shape)) for _, v in model.named_parameters())


def test_configuration_file_keeps_the_published_widths():
    with open(CONFIG) as f:
        cfg = json.load(f)
    layout = [int(i % 4 != 0) for i in range(52)]
    published = {"head_dim": 128, "hidden_size": 2560,
                 "max_position_embeddings": 16384,
                 "moe_ffn_hidden_size": 768,
                 "moe_num_active_primary_experts": 6,
                 "moe_primary_router_apply_softmax": True,
                 "norm_topk_prob": True, "num_attention_heads": 28,
                 "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
                 "rope_layout": layout, "rope_scaling": None,
                 "rope_theta": 1500000, "sliding_window_layout": layout,
                 "sliding_window_size": 4096, "tie_word_embeddings": False}
    for key, want in published.items():
        assert cfg[key] == want, key
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "moe_num_primary_experts", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "moe_num_primary_experts": 64,
                                "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"],
            cfg["vocab_size"]) == (4, 8, 18992)
    assert cfg["vocab_size"] * 8 == 151936
    assert cfg["router_width"] == 64 and cfg["held_first"] == 0
    # published layers 0..3: one whole period, the global layer first
    assert REF.layer_kinds(cfg) == [(None, False)] + [(4096, True)] * 3
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    adapter = _load("_swa_adapter", "adapters", "causal_swa_moe_lm.py")
    model_cfg = adapter._model_cfg(cfg)
    assert model_cfg.layer_kinds == REF.layer_kinds(cfg)
    assert model_cfg.held == (0, 8) and model_cfg.total_layers == 52
    assert model_cfg.parameter_count() == 370_547_200
    for key in ("deployment", "parameters", "distortion", "departures",
                "assumed", "rehearsal"):
        assert cfg[key], key
    small = dict(cfg, **cfg["rehearsal"])
    assert small["sliding_window_size"] < 64      # the band binds there too
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["smallthinker-21b-a3b"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    cells = [w for w in bench["workloads"]
             if w["config"] == "smallthinker-21b-a3b"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        ("smallthinker_21b_seq16384", "lm_zipf_seq16384", 1)]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "lm_zipf_seq16384.json")) as f:
        traffic = json.load(f)
    assert (traffic["generator"], traffic["seq_len"], traffic["zipf_s"],
            traffic["rehearsal"]) == ("lm_zipf", 16384, 1.0, {"seq_len": 64})


def test_benchmark_flop_counts_by_hand():
    with open(CONFIG) as f:
        cfg = json.load(f)
    L, W = 16384, 4096
    assert FLOPS.layer_windows(cfg) == [None, W, W, W]
    # the pairs a head's mask leaves
    assert FLOPS.attended_pairs(L, None) == L * (L + 1) / 2 == 134_225_920
    assert FLOPS.attended_pairs(L, W) == W * (W + 1) / 2 + (L - W) * W \
        == 58_722_304
    assert FLOPS.attended_pairs(L, L) == FLOPS.attended_pairs(L, None)
    # W_q, W_o 2560 x 3584; W_k, W_v 2560 x 512
    proj = 2 * (2 * 2560 * 3584 + 2 * 2560 * 512)
    assert proj == 41_943_040
    # 28 heads x (128 + 128) x 2 a pair: 14,336 a key seen
    full = proj + 14_336 * 8192.5
    band = proj + 14_336 * 58_722_304 / L
    assert FLOPS.attention_flops_per_token(cfg, L, None) == full
    assert FLOPS.attention_flops_per_token(cfg, L, W) == band
    assert 58_722_304 / L == 3584.125
    expert = 3 * 2 * 2560 * 768
    assert FLOPS.expert_flops_per_assignment(cfg) == expert == 11_796_480
    assert FLOPS.held_share(cfg) == 0.125
    forward = (full + 3 * band + 4 * (2 * 2560 * 64 + 6 * 0.125 * expert)
               + 2 * 2560 * 18992)
    assert forward == 573_305_088
    assert FLOPS.train_flops_per_token(cfg, L) == 3 * forward
    assert FLOPS.held_expert_flops(cfg, 12288) == 3 * 12288 * expert
    # unwindowed: 771.5 M; the window removes 26% of the step's work and
    # attention is 47% of what is left
    unwindowed = forward + 3 * (full - band)
    assert round(unwindowed / 1e6, 1) == 771.5
    assert 0.25 < 1 - forward / unwindowed < 0.27
    assert 0.46 < (full + 3 * band - 4 * proj) / forward < 0.48


@pytest.mark.parametrize("window", [None, 4096])
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
def test_benchmark_flash_floor_by_hand(kernel, window):
    """One call on the cell's 16,384 tokens: 28 query heads of 128 over
    the pairs the layer's mask leaves; q, dO, o, dq at 28 heads, k, v, dk,
    dv at 4; bf16 operands, float32 results, lse (and delta) a query
    row."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    got = FLOPS.flash_kernel_floor(kernel, cfg, 1, 16384, window, peaks)
    pairs = 134_225_920 if window is None else 58_722_304
    one = 2 * 128 * 28 * pairs                       # one matmul
    q, kv = 28 * 16384 * 128, 4 * 16384 * 128        # elements
    rows = 28 * 16384
    flop, moved = {
        "flash_fwd": (2 * one, 2 * (q + 2 * kv) + 4 * q + 4 * rows),
        "flash_bwd_dq": (3 * one, 2 * (2 * q + 2 * kv) + 8 * rows + 4 * q),
        "flash_bwd_dkv": (4 * one,
                          2 * (2 * q + 2 * kv) + 8 * rows + 4 * 2 * kv),
    }[kernel]
    assert (got["flop"], got["bytes"]) == (flop, moved)
    assert got["floor_s"] == flop / 197e12 > moved / 819e9   # FLOP-bound
    if window is None:
        step = FLOPS.step_flash_floor_s(kernel, cfg, 1, 16384, peaks)
        band = FLOPS.flash_kernel_floor(kernel, cfg, 1, 16384, 4096, peaks)
        assert step == pytest.approx(got["floor_s"] + 3 * band["floor_s"])


# -- the benchmark's new readers on programs without their scopes -----------

NEW_METRICS = ["swa_moe_mfu", "swa_flash_fwd_roofline",
               "swa_flash_bwd_dq_roofline", "swa_flash_bwd_dkv_roofline",
               "swa_window_attention_share", "swa_full_attention_share",
               "moe_eighth_held_expert_mxu_share"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_reads_none_never_zero_without_its_scope(metric):
    """On another configuration's program (no ``pt.attn.window`` scope, no
    windowed layout, or no trace at all) each new reader returns None and
    does not raise: the recorded DeepFM trace stands for such a program."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import trace

    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "scoped_trace.json")) as f:
        recorded = json.load(f)
    read = _load("_metric_" + metric, "metrics", metric + ".py").read

    class System:
        unit, seq, units_per_dispatch, batch = "tokens", 4096, 8192, 2
        held_assignments_per_dispatch = 8192.0

        def compiled_text(self):
            return recorded["hlo_text"]

    class Cell:
        config = {"conv_L_cache": 3}           # another configuration's

    ctx = {"trace": trace.reduce_trace(recorded["events"]), "hlo_text": "",
           "system": System(), "cell": Cell(), "rehearse": False,
           "chips": 1, "rate_per_chip": 5e4, "device_kind": "TPU v5 lite",
           "window": {"dispatches": 3}}
    assert read(ctx) is None
    assert read(dict(ctx, trace=None, _scope_shares=None)) is None

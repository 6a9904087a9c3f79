"""EvaByte on the dense path: ``models.EvaByte`` (EVA attention — the row's
own aligned window exactly, earlier windows as chunk summaries, one softmax
— under a unit-offset RMSNorm, a dense SwiGLU in every block, eight
byte-prediction heads) through ``executor.make_train_step`` / ``Trainer``
against the plain reference that sits beside the benchmark's configuration:
the heads' logits, the loss, every gradient leaf (φ and μ with them),
AdamW's first step; the flash path against the einsum path; what pooling
is at φ = 0; the shifted targets; the planted faults the cell's ``correct``
must refuse; the configuration file, the parameter counts and the FLOP
counts by hand; no ``[L, L]``-sized value in the step at 8192 positions;
the cell's rehearsal end to end; the benchmark's new readers on programs
without their scopes."""

import importlib.util
import json
import os
import subprocess
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
from paddle_tpu.models import EvaByte, EvaByteConfig, evabyte_loss
from paddle_tpu.ops import eva

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "evabyte-6.5b.json")
CELL = "evabyte_6b5_seq8192"


def _load(name, *parts):
    path = os.path.join(ROOT, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("_evabyte_reference", "configs", "evabyte-6.5b.reference.py")
FLOPS = _load("_flops_eva", "harness", "flops_eva.py")
CONTROL = _load("_eva_fault_control", "tests", "eva_fault_control.py")

#: 4 heads of 8, windows of 16 keys in chunks of 4 under 64 positions (four
#: windows: the summaries of three), 3 prediction heads
SMALL = dict(vocab_size=61, hidden_size=32, num_heads=4,
             intermediate_size=48, num_layers=2, window_size=16,
             chunk_size=4, num_pred_heads=3, max_seq_len=64, init_std=0.08,
             total_layers=32)


def _ref_cfg(cfg: EvaByteConfig):
    """The model's sizes under the configuration file's keys."""
    return {"num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_heads,
            "num_pred_heads": cfg.num_pred_heads,
            "vocab_size": cfg.vocab_size, "window_size": cfg.window_size,
            "chunk_size": cfg.chunk_size, "rms_norm_eps": cfg.rms_eps,
            "rope_theta": cfg.rope_theta, "tie_word_embeddings": False,
            "norm_add_unit_offset": True, "rope_scaling": None,
            "attention_bias": False}


def _model(seed=0, **over):
    cfg = EvaByteConfig(**dict(SMALL, **over))
    pt.seed(seed)
    model = EvaByte(cfg)
    # norms off their start, so that the unit offset is exercised
    rng = np.random.default_rng(seed + 1)
    for name, value in model.named_parameters():
        if name.endswith("norm_attn.weight") or name.endswith(
                "norm_ffn.weight") or name == "norm_f.weight":
            model._assign_by_path(name, jnp.asarray(
                rng.normal(scale=0.2, size=value.shape).astype(np.float32)))
    return model, cfg


def _batch(cfg, n=2, seed=3, L=64):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (n, L + 1)).astype(np.int32)
    return toks[:, :-1], toks[:, 1:]


def _function(model, ids, labels, use_amp=False, loss_fn=evabyte_loss):
    """(loss, logits, gradients) of ``loss_fn`` through
    ``nn.functional_call``, as the benchmark's check computes them."""
    state = nn.get_state(model)
    system = types.SimpleNamespace(model=model, loss_fn=loss_fn)
    return CONTROL.f32_function(system, state, ids, labels, use_amp), \
        state["params"]


@pytest.mark.parametrize("over", [
    {}, {"recompute": "blocks"}, {"attn_impl": "flash"},
    {"window_size": 64, "chunk_size": 16},       # one window: no summary
    {"num_pred_heads": 8}], ids=lambda o: "-".join(map(str, o.values()))
    or "published_shape")
def test_float32_function_matches_reference(over):
    """Loss, the heads' logits and every gradient leaf — φ, μ and the
    unit-offset norms among them — against the reference's explicit mask,
    on four windows (one where the window is the sequence), by the einsum
    path and by the flash kernels (interpreted, float32 operands)."""
    model, cfg = _model(**over)
    ids, labels = _batch(cfg)
    got, params = _function(model, ids, labels)
    ref = REF.loss_and_grads(params, ids, labels, _ref_cfg(cfg))
    verdict = REF.compare(got, ref, "f32")
    assert verdict["leaves"] == 11 * cfg.num_layers + 3
    assert verdict["grad_leaf_rel"] <= 2e-5 and verdict["logit_rel"] <= 2e-6
    assert verdict["loss_rel"] <= REF.TOL["f32"]["loss_rel"]
    pooling = [f"blocks.{i}.attn.{name}" for i in range(cfg.num_layers)
               for name in ("adaptive_phi", "adaptive_mu_k")]
    if cfg.window_size < 64:
        assert verdict["ok"], verdict
        assert all(np.abs(np.asarray(got["grads"][k])).max() > 0
                   for k in pooling)
    else:
        # one window reads no summary: φ and μ have no gradient, here or
        # there (``compare`` wants every leaf to have one: not ``ok``)
        assert verdict["leaves_compared"] == verdict["leaves"] - len(pooling)
        assert all(np.abs(np.asarray(tree[k])).max() == 0
                   for k in pooling for tree in (got["grads"], ref["grads"]))


def test_adamw_step_matches_reference():
    """``make_train_step`` with AdamW from zero moments: the loss it
    returns, the gradient in its first moment and the update it leaves,
    held to the reference as the cell's check holds them."""
    model, cfg = _model()
    ids, labels = _batch(cfg)
    hyper = {"lr": 4e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1}
    opt = optimizer.AdamW(learning_rate=hyper["lr"],
                          weight_decay=hyper["weight_decay"],
                          beta1=hyper["beta1"], beta2=hyper["beta2"],
                          epsilon=hyper["eps"])
    step = make_train_step(model, opt, evabyte_loss, donate=False)
    state = nn.get_state(model)
    with jax.default_matmul_precision("highest"):
        new_state, new_opt, loss = step(
            state, opt.init(state["params"]), jax.random.key(0),
            (jnp.asarray(ids),), (jnp.asarray(labels),))
    ref = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg))
    assert abs(float(loss) - ref["loss"]) <= 3e-6 * ref["loss"]
    slots = new_opt["slots"]
    update = REF.compare_update(state["params"], new_state["params"],
                                slots["m"], slots["v"], hyper)
    assert update["ok"], update
    for k, g in ref["grads"].items():
        np.testing.assert_allclose(np.asarray(slots["m"][k]) / 0.1,
                                   np.asarray(g), rtol=2e-3, atol=2e-7)


def test_trainer_trains_under_amp():
    model, cfg = _model(recompute="blocks")
    trainer = Trainer(model, optimizer.AdamW(learning_rate=3e-3),
                      evabyte_loss, amp=True)
    ids, labels = _batch(cfg, n=4)
    losses = [float(trainer.train_step(ids, labels)) for _ in range(8)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05


def test_flash_and_einsum_paths_agree_under_amp():
    """The attention sublayer alone, bf16 kernels against the float32
    einsum: out and the gradient of every input, φ and μ with them."""
    rng = np.random.default_rng(5)
    B, L, H, d = 2, 64, 2, 8
    q, k, v, w = (jnp.asarray(rng.normal(size=(B, L, H, d)).astype(
        np.float32)) for _ in range(4))
    phi, mu = (jnp.asarray(rng.normal(size=(H, d)).astype(np.float32))
               for _ in range(2))

    def run(f):
        return (f(q, k, v, phi, mu),) + jax.grad(
            lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4))(
                q, k, v, phi, mu)

    got = run(lambda *a: eva.eva_attention(*a, 16, 4))
    want = run(lambda *a: eva.eva_attention_einsum(*a, 16, 4))
    for a, b in zip(got, want):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 0.03 * scale


def test_zero_phi_is_mean_pooling_plus_mu():
    rng = np.random.default_rng(6)
    k, v = (jnp.asarray(rng.normal(size=(1, 32, 2, 8)).astype(np.float32))
            for _ in range(2))
    mu = jnp.asarray(rng.normal(size=(2, 8)).astype(np.float32))
    ks, vs = eva.chunk_summaries(k, v, jnp.zeros((2, 8)), mu, 4, 8 ** -0.5)
    np.testing.assert_allclose(
        np.asarray(ks), np.asarray(k.reshape(1, 8, 4, 2, 8).mean(2) + mu),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(vs), np.asarray(v.reshape(1, 8, 4, 2, 8).mean(2)),
        atol=1e-6)
    # and the reference pools alike
    rk, rv = REF.summaries(k, v, jnp.zeros((2, 8)), mu, 4)
    np.testing.assert_allclose(np.asarray(rk), np.asarray(ks), atol=1e-6)
    np.testing.assert_allclose(np.asarray(rv), np.asarray(vs), atol=1e-6)


def test_the_mask_in_words():
    """The reference's mask, row by row: the first window sees no summary;
    a later one every chunk of the windows before it — the last chunk that
    ends on the window's edge included — and none of its own; keys only of
    the own window, up to the row."""
    seen = np.asarray(REF.seen(0, 64, 64, 16, 4))
    S = 16
    assert not seen[:16, :S].any()                    # the first window
    for i in (16, 17, 31):                            # the second
        assert seen[i, :S].tolist() == [True] * 4 + [False] * 12
        assert seen[i, S:].tolist() == [16 <= j <= i for j in range(64)]
    assert seen[63, :S].tolist() == [True] * 12 + [False] * 4
    assert seen[:, :S].sum() == FLOPS.attended_products(64, 16, 4)["summary"]
    assert seen[:, S:].sum() == FLOPS.attended_products(64, 16, 4)["local"]


def test_the_loss_is_the_mean_of_the_heads_shifted_losses():
    rng = np.random.default_rng(7)
    B, L, P, V = 2, 12, 8, 11
    logits = jnp.asarray(rng.normal(size=(B, L, P, V)).astype(np.float32))
    toks = rng.integers(0, V, (B, L + 1))
    labels = toks[:, 1:]
    per_head = []
    for r in range(P):          # head r at t predicts byte t + 1 + r
        lp = jax.nn.log_softmax(logits[:, :L - r, r], axis=-1)
        want = toks[:, 1 + r:]
        per_head.append(-np.mean(np.take_along_axis(
            np.asarray(lp), want[..., None], axis=-1)))
    got = float(evabyte_loss(logits, jnp.asarray(labels)))
    assert got == pytest.approx(np.mean(per_head), rel=1e-6)
    np.testing.assert_array_equal(
        np.asarray(REF.head_targets(jnp.asarray(labels), P))[:, :, 3],
        np.concatenate([labels[:, 3:], -np.ones((B, 3), np.int64)], axis=1))


# -- the planted faults of the cell's ``correct`` ----------------------------

@pytest.mark.parametrize("fault", CONTROL.FAULTS)
def test_planted_fault_is_refused_by_the_reference(fault):
    """Each of the wrong programs the cell's ``correct`` must refuse
    (ISSUE 46, Tentpole 4), planted at a small size and judged by the
    reference's own ``compare``; the sound program passes."""
    model, cfg = _model(num_pred_heads=8)
    ids, labels = _batch(cfg)
    system = types.SimpleNamespace(model=model, loss_fn=evabyte_loss)
    state = nn.get_state(model)
    ref = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg))
    if fault == "reference_in_float8":
        got = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg),
                                 operand_dtype=jnp.float8_e4m3fn)
        assert not REF.compare(got, ref, "amp")["ok"]
        return
    with CONTROL.planted(system, fault):
        got = CONTROL.f32_function(
            system, state, ids, labels,
            fault == "bf16_where_the_file_says_float32")
    verdict = REF.compare(got, ref, "f32")
    assert verdict["ok"] == (fault == "none"), verdict
    if fault in ("mu_dropped", "summary_one_window_early", "seven_heads"):
        assert verdict["grad_leaf_l2"] > 100 * REF.TOL["f32"]["grad_leaf_l2"]


@pytest.mark.parametrize("bad", [
    dict(window_size=10), dict(num_heads=3), dict(num_pred_heads=0),
    dict(recompute="experts")])
def test_what_the_model_cannot_run_is_refused(bad):
    with pytest.raises(EnforceNotMet):
        EvaByte(EvaByteConfig(**dict(SMALL, **bad)))


def test_a_sequence_of_no_whole_windows_is_refused():
    model, cfg = _model()
    with pytest.raises(EnforceNotMet, match="whole windows"):
        model(jnp.zeros((1, 40), jnp.int32))


def test_residual_init_scales_the_projections_into_the_stream():
    model, cfg = _model(hidden_size=64, intermediate_size=256)
    std = lambda name: float(jnp.std(dict(model.named_parameters())[name]))
    assert cfg.out_std == pytest.approx(0.08 / 8)
    assert std("blocks.0.attn.wo") == pytest.approx(cfg.out_std, rel=0.1)
    assert std("blocks.0.mlp.w_down") == pytest.approx(cfg.out_std, rel=0.1)
    assert std("blocks.0.attn.wq") == pytest.approx(0.08, rel=0.1)


@pytest.mark.parametrize("which", ["cut", "whole", "allocated"])
def test_parameter_counts(which):
    if which == "cut":
        layer = 67_108_864 + 8_192 + 135_266_304 + 8_192
        assert layer == 202_391_552
        assert EvaByteConfig(num_layers=4).parameter_count() \
            == 4 * layer + 1_310_720 + 10_485_760 + 4_096 == 821_366_784
    elif which == "whole":
        assert EvaByteConfig().parameter_count() \
            == 32 * 202_391_552 + 11_800_576 == 6_488_330_240
    else:
        for over in ({}, dict(num_pred_heads=8), dict(num_layers=3)):
            model, cfg = _model(**over)
            assert cfg.parameter_count() == sum(
                int(np.prod(v.shape)) for _, v in model.named_parameters())


def test_configuration_file_keeps_the_published_widths():
    with open(CONFIG) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096, "init_std": 0.01275,
        "intermediate_size": 11008, "max_position_embeddings": 32768,
        "max_seq_length": 32768, "mixedp_attn": True,
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048}
    for key, want in published.items():
        assert cfg[key] == want, key
    assert set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["num_hidden_layers"] == 4 and cfg["recompute"] == "blocks"
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    adapter = _load("_eva_adapter", "adapters", "causal_eva_lm.py")
    model_cfg = adapter._model_cfg(cfg)
    assert model_cfg.parameter_count() == 821_366_784
    assert model_cfg.total_layers == 32 and model_cfg.head_dim == 128
    for key in ("deployment", "parameters", "distortion", "departures",
                "assumed", "rehearsal"):
        assert cfg[key], key
    small = dict(cfg, **cfg["rehearsal"])
    assert 128 // small["window_size"] >= 4          # four windows there too
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["evabyte-6.5b"]
    assert entry["reduced"] == list(cfg["reduced"])
    assert entry["source"] == \
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    cells = [w for w in bench["workloads"] if w["config"] == "evabyte-6.5b"]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "lm_zipf_seq8192", 1)]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new = [m["name"] for m in bench["per_layer"]
           if m.get("workloads") == [CELL]]
    assert new == NEW_METRICS
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "lm_zipf_seq8192.json")) as f:
        traffic = json.load(f)
    assert (traffic["generator"], traffic["seq_len"], traffic["zipf_s"],
            traffic["rehearsal"]) == ("lm_zipf", 8192, 1.0, {"seq_len": 128})


def test_benchmark_flop_counts_by_hand():
    with open(CONFIG) as f:
        cfg = json.load(f)
    products = FLOPS.attended_products(8192, 2048, 16)
    assert products == {"local": 4 * (2048 * 2049 // 2),
                        "summary": 2048 * 128 * (0 + 1 + 2 + 3),
                        "all": 9_965_568}
    assert (products["local"], products["summary"]) == (8_392_704, 1_572_864)
    block = FLOPS.block_flops_per_token(cfg, 8192)
    assert block == {"projections": 134_217_728.0,
                     "attention": 2 * 2 * 128 * 32 * 9_965_568 / 8192,
                     "ffn": 270_532_608.0}
    assert block["attention"] == 19_931_136.0
    heads = 2 * 4096 * 2560
    assert FLOPS.forward_flops_per_token(cfg, 8192) \
        == 4 * 424_681_472 + heads == 1_719_697_408
    assert FLOPS.train_flops_per_token(cfg, 8192) == 3 * 1_719_697_408
    # the kernels are 4.7% of a block, the mixer's matmuls 36%
    assert block["attention"] / sum(block.values()) == pytest.approx(
        0.0469, abs=1e-4)
    assert (block["attention"] + block["projections"]) / sum(
        block.values()) == pytest.approx(0.363, abs=1e-3)


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
def test_benchmark_flash_floor_by_hand(kernel):
    with open(CONFIG) as f:
        cfg = json.load(f)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    got = FLOPS.flash_kernel_floor(kernel, cfg, 1, 8192, peaks)
    one = 2 * 128 * 32 * 9_965_568          # one matmul over the products
    q = 32 * 8192 * 128                     # elements
    kv = 32 * (8192 + 384) * 128            # keys and the summaries read
    rows = 32 * 8192
    flop, moved = {
        "flash_fwd": (2 * one, 2 * (q + 2 * kv) + 4 * q + 4 * rows),
        "flash_bwd_dq": (3 * one, 2 * (2 * q + 2 * kv) + 8 * rows + 4 * q),
        "flash_bwd_dkv": (4 * one,
                          2 * (2 * q + 2 * kv) + 8 * rows + 4 * 2 * kv),
    }[kernel]
    assert (got["flop"], got["bytes"]) == (flop, moved)
    assert got["floor_s"] == flop / 197e12 > moved / 819e9   # FLOP-bound


def test_no_value_of_the_step_is_as_large_as_the_scores():
    """Abstract evaluation of the train step's function at 8192 positions
    through the flash path (small widths, the published window and chunk):
    no value anywhere in the jaxpr outside a kernel's body (whose tiles
    live in VMEM) has two axes of 512 or more, so nothing the size of
    ``[L, L]`` or ``[L, L/16]`` is ever made, forward or backward; the kernels are handed 384 summaries
    beside 8192 keys and walk 52 of 272 block pairs."""
    from paddle_tpu.core import profiler

    cfg = EvaByteConfig(vocab_size=320, hidden_size=64, num_heads=2,
                        intermediate_size=96, num_layers=1, window_size=2048,
                        chunk_size=16, num_pred_heads=1, max_seq_len=8192,
                        attn_impl="flash", recompute="blocks")
    model = EvaByte(cfg)
    state = nn.get_state(model)

    def grads(params, ids, labels):
        def loss(params):
            out, _ = nn.functional_call(
                model, {"params": params, "buffers": state["buffers"]}, ids,
                training=True)
            return evabyte_loss(out, labels)

        with amp.step_ctx(True):
            return jax.value_and_grad(loss)(params)

    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    before = len(profiler.host_spans())
    jaxpr = jax.make_jaxpr(grads)(state["params"], ids, ids)
    spans = [s.counts for s in profiler.host_spans()[before:]
             if s.name == "pt.flash.operands"]
    assert spans and all(
        (c["pairs_walked"], c["pairs_rectangle"], c["summary_keys"])
        == (52, 272, 384) for c in spans)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                assert sum(n >= 512 for n in shape) < 2, (eqn.primitive,
                                                           shape)
            if eqn.primitive.name != "pallas_call":     # a body's tiles
                for sub in jax.core.jaxprs_in_params(eqn.params):   # are
                    walk(sub)                                  # VMEM's

    walk(jaxpr.jaxpr)


def test_rms_norm_without_the_offset_is_the_program_it_was():
    """``nn.RMSNorm(unit_offset=False)``, every existing caller's: the
    jaxpr of ``functional.rms_norm`` on its weight, nothing added; with the
    offset the scale is ``1 + w`` from zeros."""
    x = jnp.ones((2, 3, 8))
    plain = nn.RMSNorm(8, 1e-6)
    assert str(jax.make_jaxpr(lambda x, w: nn.functional.rms_norm(
        x, w, 1e-6))(x, plain.weight)) == str(jax.make_jaxpr(
            lambda x, w: nn.functional_call(
                plain, {"params": {"weight": w}, "buffers": {}}, x)[0])(
                    x, plain.weight))
    offset = nn.RMSNorm(8, 1e-6, unit_offset=True)
    assert float(jnp.max(jnp.abs(offset.weight))) == 0.0
    np.testing.assert_allclose(np.asarray(offset(x)), np.asarray(plain(x)))


@pytest.mark.parametrize("floored", [False, True])
def test_a_leaf_under_the_gradient_floor_is_left_out_by_name(floored):
    """``compare(floored=True)``, the trained state's: a leaf whose
    reference gradient's root-mean-square entry lies under
    ``GRADIENT_FLOOR`` is named in ``leaves_floored`` and what is wrong in
    it refuses nothing; a leaf over the floor still does, and without
    ``floored`` every leaf is compared."""
    quiet = REF.GRADIENT_FLOOR / 10
    ref = {"loss": 5.0, "logits": jnp.ones((1, 4, 2, 3)),
           "grads": {"live": jnp.full((8, 8), 1e-3),
                     "collapsed": jnp.full((8, 8), quiet)}}
    got = {"loss": 5.0, "logits": ref["logits"],
           "grads": {"live": ref["grads"]["live"],
                     "collapsed": -ref["grads"]["collapsed"]}}
    verdict = REF.compare(got, ref, "f32", floored)
    assert verdict["ok"] is floored
    assert verdict.get("leaves_floored") == (["collapsed"] if floored
                                             else None)
    assert verdict["leaves_compared"] == (1 if floored else 2)
    got["grads"]["live"] = ref["grads"]["live"] * 1.01
    assert not REF.compare(got, ref, "f32", floored)["ok"]


def test_the_check_compares_at_both_states_and_hands_the_trainer_back():
    """``adapters/causal_eva_lm``: after real steps at the configured rate
    the check compares at the trainer's own parameters and at those the
    window started from (made again from the seed, bit for bit), and the
    trainer's own come back whole."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import spec

    cell = spec.Cell(spec.load_benchmark(), CELL, rehearse=True)
    system = cell.adapter().build(cell, 7, jax.devices()[:1], True,
                                  cell.generator(), {})
    start = jax.device_get(system.trainer.state["params"])
    for item in system.host_items:
        system.dispatch(item)
    trained = jax.device_get(system.trainer.state["params"])
    again = jax.device_get(system._initial_state()["params"])
    assert set(again) == set(start)
    for k in start:
        np.testing.assert_array_equal(again[k], start[k], err_msg=k)
    assert any((trained[k] != start[k]).any() for k in start)
    out = system.check_reference(cell.reference())
    assert out["ok"] and out["trained"]["ok"] and out["initial"]["ok"]
    losses = {state: out[state]["f32"]["loss"][1]
              for state in ("trained", "initial")}
    assert losses["trained"] != losses["initial"]
    assert out["trained"]["f32"]["leaves_floored"] == []    # a floor that
    #              this tiny model's gradients (1e-6 an entry) never reach
    assert "leaves_floored" not in out["initial"]["f32"]
    for k, v in system.trainer.state["params"].items():
        np.testing.assert_array_equal(np.asarray(v), trained[k], err_msg=k)
    assert system.check_state()["ok"]


def test_the_cell_rehearses_end_to_end():
    """``benchmarks/run.py --workload evabyte_6b5_seq8192 --rehearse``:
    the adapter, the generator at 128 positions, the window, the check
    against the reference and the result line, on the CPU."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "0", "--rehearse"], capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True
    assert set(line["metrics"]) == {"rehearsal.tokens_per_s_per_chip",
                                    "rehearsal.setup_s"}
    numbers = line["reference"]["numbers"]
    # at the trainer's own parameters after the window and at the seed's
    assert {f"{state}.{name}" for state in ("trained", "initial") for name
            in ("f32.grad_leaf_l2", "amp.logit_rel", "update.param_rel")} \
        <= set(numbers)
    assert all(r <= limit for name, (r, limit) in numbers.items()
               if not name.endswith(".ok"))


# -- the benchmark's new readers on programs without their scopes -----------

NEW_METRICS = ["eva_mfu", "eva_flash_fwd_roofline",
               "eva_flash_bwd_dq_roofline", "eva_flash_bwd_dkv_roofline",
               "eva_prep_share", "eva_mixer_share"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_reads_none_never_zero_without_its_scope(metric):
    """On another configuration's program (no ``pt.eva.prep`` scope, no
    ``attention_class``, or no trace at all) each new reader returns None
    and does not raise: the recorded trace stands for such a program.
    ``eva_mixer_share`` reads ``pt.attn`` whole, which that program has:
    there it is that program's mixer share, and None without a trace."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    from harness import trace

    with open(os.path.join(ROOT, "benchmarks", "testdata",
                           "scoped_trace.json")) as f:
        recorded = json.load(f)
    read = _load("_metric_" + metric, "metrics", metric + ".py").read

    class System:
        unit, seq, units_per_dispatch, batch = "tokens", 4096, 8192, 2

        def compiled_text(self):
            return recorded["hlo_text"]

    class Cell:
        config = {"conv_L_cache": 3}           # another configuration's

    ctx = {"trace": trace.reduce_trace(recorded["events"]), "hlo_text": "",
           "system": System(), "cell": Cell(), "rehearse": False,
           "chips": 1, "rate_per_chip": 5e4, "device_kind": "TPU v5 lite",
           "window": {"dispatches": 3}}
    if metric == "eva_mixer_share":
        assert 0.0 < read(ctx) < 1.0
    else:
        assert read(ctx) is None
    assert read(dict(ctx, trace=None, _scope_shares=None)) is None


def test_eva_mfu_and_rooflines_by_hand():
    """On the cell's own configuration: ``eva_mfu`` is FLOPs a token x
    rate over the peak; a kernel's share is its floor x layers over its
    measured time a step."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    with open(CONFIG) as f:
        cfg = json.load(f)

    class System:
        unit, seq, batch = "tokens", 8192, 1

    class Cell:
        config = cfg

    ctx = {"system": System(), "cell": Cell(), "rehearse": False,
           "chips": 1, "rate_per_chip": 18_000.0,
           "device_kind": "TPU v5 lite", "window": {"dispatches": 8},
           "trace": {"op_self_s": {"%flash_fwd.3 = x": 0.16,
                                   "%flash_bwd_dq.1 = y": 0.08}}}
    mfu = _load("_m_eva_mfu", "metrics", "eva_mfu.py").read(ctx)
    assert mfu == pytest.approx(3 * 1_719_697_408 * 18_000 / 197e12)
    fwd = _load("_m_eva_fwd", "metrics", "eva_flash_fwd_roofline.py").read(ctx)
    floor = 4 * 2 * 2 * 128 * 32 * 9_965_568 / 197e12
    assert fwd == pytest.approx(100 * floor / (0.16 / 8))
    assert _load("_m_eva_dkv", "metrics",
                 "eva_flash_bwd_dkv_roofline.py").read(ctx) is None
    assert _load("_m_eva_mfu2", "metrics", "eva_mfu.py").read(
        dict(ctx, rehearse=True)) is None

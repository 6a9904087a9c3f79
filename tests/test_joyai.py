"""JoyAI-LLM-Flash on the dense path: ``models.Joyai`` through
``executor.make_train_step`` / ``Trainer`` against the plain reference
that sits beside the benchmark's configuration; the held experts' shares
adding up to the uncut layer; the sigmoid routing rule by hand; a skewed
batch past the bounded dispatch buffer; adjacent-pair rotary;
the prediction loss's shift and mask; the flash kernels at q.k 192 / v 128
(interpret mode) against einsum; the configuration file and the FLOP
counts by hand."""

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
from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.executor import Trainer, make_train_step
from paddle_tpu.models import Joyai, JoyaiConfig, joyai_loss
from paddle_tpu.models.joyai import MTP_LOSS_WEIGHT, joyai_losses
from paddle_tpu.models.transformer import rotary_pairs
from paddle_tpu.ops.flash_attention import flash_attention
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


REF = _load("_joyai_reference", "configs", "joyai-llm-flash.reference.py")
FLOPS = _load("_flops_mla", "harness", "flops_mla.py")

#: 1 dense + 2 expert layers + the prediction module; q.k 24 / v 16
SMALL = dict(vocab_size=97, hidden_size=32, num_heads=4, num_layers=3,
             dense_size=48, q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8,
             v_dim=16, num_experts=8, experts_per_token=2, expert_size=16,
             max_seq_len=16, init_std=0.05)


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
            "mtp_loss_weight": MTP_LOSS_WEIGHT,
            "bias_update_rate": cfg.bias_update_rate}


def _batch(cfg: JoyaiConfig, batch: int, seed: int):
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
    step = make_train_step(model, opt, joyai_loss, donate=False, amp=amp)
    new_state, _, loss = step(state, opt.init(state["params"]),
                              jax.random.key(0), (jnp.asarray(ids),),
                              (jnp.asarray(labels),))
    grads = {k: (np.asarray(state["params"][k]) - np.asarray(v)) / LR
             for k, v in new_state["params"].items()}
    return float(loss), grads, new_state["buffers"], state


@pytest.mark.parametrize("held", [(0, 8), (2, 2), (6, 2)],
                         ids=["whole", "held_2_3", "held_6_7"])
def test_train_step_matches_reference(held):
    """Both losses, EVERY gradient leaf and the router biases after the
    step, float32, against the reference at 1e-5: the same function by
    another route (a bounded buffer of sorted rows, grouped matmuls and
    sums by token against every-held-expert-masked)."""
    pt.seed(3)
    cfg = JoyaiConfig(**SMALL, held=held)
    model = Joyai(cfg)
    ids, labels = _batch(cfg, 2, 5)
    total, grads, buffers, state = _sgd_step(model, ids, labels)
    ref = REF.loss_and_grads(state["params"], ids, labels, _ref_cfg(cfg),
                             buffers=state["buffers"])
    assert set(grads) == set(ref["grads"])
    assert abs(total - ref["total"]) <= 1e-5 * abs(ref["total"])
    out, _ = nn.functional_call(model, state, jnp.asarray(ids), training=True)
    main, mtp = joyai_losses(out, jnp.asarray(labels))
    assert abs(float(main) - ref["loss"]) <= 1e-5 * ref["loss"]
    assert abs(float(mtp) - ref["loss_mtp"]) <= 1e-5 * ref["loss_mtp"]
    assert abs(total - (ref["loss"] + 0.3 * ref["loss_mtp"])) <= 1e-5 * total
    for name, r in ref["grads"].items():
        top = np.max(np.abs(r))
        assert top > 0, name
        assert np.max(np.abs(grads[name] - r)) <= 1e-5 * top, name
    # counters: every assignment counted, those that landed here computed
    T, k = ids.size, cfg.experts_per_token
    counts = np.asarray(buffers["expert_counts"])
    assert counts.shape == (cfg.expert_layers, cfg.num_experts) == (3, 8)
    assert (counts.sum(axis=1) == T * k).all()
    np.testing.assert_array_equal(counts, ref["counts"])
    first, n = held
    np.testing.assert_array_equal(np.asarray(buffers["held_assignments"]),
                                  counts[:, first:first + n].sum(axis=1))
    assert (np.asarray(buffers["held_assignments"])
            <= np.asarray(buffers["dispatch_rung"])).all()
    assert int(buffers["tokens_dropped"]) == 0
    # the bias moved by the rate towards even loads, bit for bit
    for name, want in ref["bias_after"].items():
        np.testing.assert_array_equal(np.asarray(buffers[name]), want, name)
        moved = np.asarray(buffers[name]) - np.asarray(state["buffers"][name])
        # by the rate, up or down; an expert at the mean load stays put
        assert np.all(np.minimum(np.abs(np.abs(moved) - cfg.bias_update_rate),
                                 np.abs(moved)) < 1e-8)
        assert np.any(moved > 0) and np.any(moved < 0)


def test_trainer_trains_both_heads_and_updates_the_bias():
    """Through ``Trainer`` (donated step, AdamW, amp): the two-part loss
    falls, the bias buffers move every step, one compile."""
    pt.seed(0)
    cfg = JoyaiConfig(**SMALL, held=(2, 2))
    model = Joyai(cfg)
    ids, labels = _batch(cfg, 4, 1)
    tr = Trainer(model, optimizer.AdamW(learning_rate=3e-3, weight_decay=0.1,
                                        beta2=0.95), joyai_loss, amp=True)
    first = float(tr.train_step(ids, labels))
    for _ in range(30):
        last = float(tr.train_step(ids, labels))
    assert abs(first - 1.3 * math.log(cfg.vocab_size)) < 0.3
    assert last < 0.7 * first
    assert tr._train_step._cache_size() == 1
    assert int(tr.state["buffers"]["tokens_dropped"]) == 0
    bias = np.asarray(tr.state["buffers"]["blocks.1.moe." + _BIAS])
    assert np.max(np.abs(bias)) > 5 * cfg.bias_update_rate
    text = tr.compiled_text(ids, labels)
    for scope in ("pt.mla.q", "pt.mla.kv", "pt.moe.shared", "pt.mtp"):
        assert scope in text, scope


def _layer_case(T=64, d=16, E=8, f=8, seed=0):
    r = np.random.default_rng(seed)
    p = {"router_w": r.normal(size=(d, E)) * 0.5,
         "w_gate": r.normal(size=(E, d, f)) * 0.3,
         "w_up": r.normal(size=(E, d, f)) * 0.3,
         "w_down": r.normal(size=(E, f, d)) * 0.3,
         "shared.w_gate": r.normal(size=(d, f)) * 0.3,
         "shared.w_up": r.normal(size=(d, f)) * 0.3,
         "shared.w_down": r.normal(size=(f, d)) * 0.3}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    x = jnp.asarray(r.normal(size=(T, d)), jnp.float32)
    bias = jnp.asarray(r.normal(size=(E,)) * 0.05, jnp.float32)
    return p, x, bias


def _uncut_layer(p, x, bias, k, E):
    """The reference's whole expert layer: every expert held."""
    cfg = {"num_experts_per_tok": k, "held_first": 0, "n_routed_experts": E,
           "routed_scaling_factor": 2.5}
    return REF._experts(p, "", x, bias, cfg, None, lambda a: a)[0]


@pytest.mark.parametrize("held", [(0, 8), (2, 2), (6, 2)],
                         ids=["whole", "held_2_3", "held_6_7"])
def test_rows_walked_is_stacked_a_layer_within_the_rung(held):
    """``dispatch_rows_walked`` leaves the step beside ``dispatch_rung``:
    one count an expert layer (the module's last), the whole chunks the
    bounded buffer's row movement passed over — never more than the rung,
    and the rung itself where every assignment is live."""
    pt.seed(3)
    cfg = JoyaiConfig(**SMALL, held=held)
    ids, labels = _batch(cfg, 2, 5)
    _, _, buffers, _ = _sgd_step(Joyai(cfg), ids, labels)
    walked = np.asarray(buffers["dispatch_rows_walked"])
    rung = np.asarray(buffers["dispatch_rung"])
    assert walked.shape == rung.shape == (cfg.expert_layers,)
    assert walked.dtype == np.int32 and (walked <= rung).all()
    landed = np.asarray(buffers["held_assignments"])
    if held == (0, cfg.num_experts):
        np.testing.assert_array_equal(walked, rung)
    else:       # a 32-row buffer is one chunk: walked whole, or not at all
        np.testing.assert_array_equal(walked, np.where(landed > 0, rung, 0))


def _share(p, x, bias, k, held):
    first, n = held
    return moe.held_moe(x, p["router_w"], bias, p["w_gate"][first:first + n],
                        p["w_up"][first:first + n],
                        p["w_down"][first:first + n], k, held, 2.5)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the routed parts that the four shares
    give, plus the shared expert ONCE (every chip computes it alike),
    equal the uncut reference's layer output."""
    p, x, bias = _layer_case()
    k, E = 2, 8
    want = np.asarray(_uncut_layer(p, x, bias, k, E))
    shared = REF._swiglu(x, p["shared.w_gate"], p["shared.w_up"],
                         p["shared.w_down"], lambda a: a)
    parts, landed = [], 0
    for first in range(0, E, 2):
        out, route = _share(p, x, bias, k, (first, 2))
        parts.append(np.asarray(out))
        landed += int(route["held_assignments"])
        assert int(route["dropped"]) == 0
        assert int(np.asarray(route["counts"]).sum()) == x.shape[0] * k
    assert landed == x.shape[0] * k       # every assignment lands once
    assert all(np.max(np.abs(part)) > 0 for part in parts)
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), want,
                               rtol=1e-5, atol=1e-5)


def test_holding_every_expert_is_the_uncut_layer():
    p, x, bias = _layer_case(seed=1)
    k, E = 2, 8
    out, route = _share(p, x, bias, k, (0, E))
    shared = REF._swiglu(x, p["shared.w_gate"], p["shared.w_up"],
                         p["shared.w_down"], lambda a: a)
    np.testing.assert_allclose(np.asarray(out) + np.asarray(shared),
                               np.asarray(_uncut_layer(p, x, bias, k, E)),
                               rtol=1e-5, atol=1e-5)
    assert moe.dispatch_ladder(x.shape[0], k, E, E) == (x.shape[0] * k,)
    assert int(route["held_assignments"]) == int(route["rung"]) == 128


def test_routing_rule_by_hand():
    """Four experts, two a token. The bias moves the CHOICE and never the
    weight; the weights are the chosen scores over their sum, times the
    scale; ties go to the lower expert."""
    sig = lambda z: 1.0 / (1.0 + math.exp(-z))
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0],
                          [0.5, 0.5, 0.5, 0.5]], jnp.float32)
    none = moe.sigmoid_route(logits, jnp.zeros(4), 2, 2.5)
    assert np.asarray(none["index"]).tolist() == [[0, 1], [0, 1]]
    a, b = sig(2.0), sig(1.0)
    np.testing.assert_allclose(np.asarray(none["weight"][0]),
                               [2.5 * a / (a + b), 2.5 * b / (a + b)],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(none["weight"][1]), [1.25, 1.25],
                               rtol=1e-6)
    # a bias of 0.6 lifts expert 3 over experts 1 and 2 in token 0
    # (sig(-1) + 0.6 = 0.869 > sig(1) = 0.731) and to the top in token 1
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6], jnp.float32)
    got = moe.sigmoid_route(logits, bias, 2, 2.5)
    assert np.asarray(got["index"]).tolist() == [[0, 3], [3, 0]]
    d = sig(-1.0)
    np.testing.assert_allclose(np.asarray(got["weight"][0]),
                               [2.5 * a / (a + d), 2.5 * d / (a + d)],
                               rtol=1e-6)          # no 0.6 in the weight
    np.testing.assert_allclose(np.asarray(got["weight"][1]), [1.25, 1.25],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got["weight"]).sum(axis=1),
                               [2.5, 2.5], rtol=1e-6)
    assert np.asarray(got["counts"]).tolist() == [2, 0, 0, 2]
    # the bias has no gradient; the logits' passes through the weights
    g = jax.grad(lambda b: jnp.sum(moe.sigmoid_route(logits, b, 2, 2.5)
                                   ["weight"] ** 2))(bias)
    assert np.asarray(g).tolist() == [0.0] * 4


def _dense_mask(x, router, bias, w_gate, w_up, w_down, k, held):
    s = jax.nn.sigmoid(x @ router)
    _, idx = jax.lax.top_k(s + bias, k)
    g = s * jnp.sum(jax.nn.one_hot(idx, s.shape[-1]), axis=1)
    g = (g / (g.sum(-1, keepdims=True) + 1e-20) * 2.5)[
        :, held[0]:held[0] + held[1]]
    act = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_gate)) * jnp.einsum(
        "td,edf->tef", x, w_up)
    return jnp.einsum("tef,efd->td", act * g[..., None], w_down)


def test_a_skewed_batch_past_the_buffer_is_computed_whole():
    """256 tokens, 4 experts a token of 16, experts 0 and 1 held: the
    sorted buffer has 256 rows (twice the 128 of even loads). A bias pushes
    every token onto the two held experts — 2 x 256 assignments, past the
    buffer — and the other form runs (every held expert on every token,
    512 rows): nothing dropped, output and gradients equal the dense-mask
    reference."""
    r = np.random.default_rng(0)
    T, d, E, k, f, held = 256, 16, 16, 4, 8, (0, 2)
    assert moe.dispatch_ladder(T, k, E, 2) == (256, 512)
    x = jnp.asarray(r.normal(size=(T, d)), jnp.float32)
    router = jnp.asarray(r.normal(size=(d, E)) * 0.3, jnp.float32)
    banks = [jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
             for s in ((2, d, f), (2, d, f), (2, f, d))]
    even, skew = jnp.zeros(E), jnp.zeros(E).at[:2].set(1.0)
    _, route = jax.jit(lambda *a: moe.held_moe(*a, k, held, 2.5))(
        x, router, even, *banks)
    assert int(route["held_assignments"]) <= 256 == int(route["rung"])
    out, route = jax.jit(lambda *a: moe.held_moe(*a, k, held, 2.5))(
        x, router, skew, *banks)
    assert int(route["held_assignments"]) == 2 * T > 256
    assert int(route["rung"]) == 512 and int(route["dropped"]) == 0
    assert np.asarray(route["counts"])[:2].tolist() == [T, T]
    want = _dense_mask(x, router, skew, *banks, k, held)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    loss = lambda fn: lambda x, router, *b: jnp.sum(jnp.sin(fn(x, router,
                                                               *b)))
    got = jax.grad(loss(lambda x, router, *b: moe.held_moe(
        x, router, skew, *b, k, held, 2.5)[0]), argnums=(0, 1, 2, 3, 4))(
            x, router, *banks)
    ref = jax.grad(loss(lambda x, router, *b: _dense_mask(
        x, router, skew, *b, k, held)), argnums=(0, 1, 2, 3, 4))(
            x, router, *banks)
    for a, b in zip(got, ref):
        b = np.asarray(b)
        assert np.max(np.abs(np.asarray(a) - b)) <= 5e-5 * np.max(np.abs(b))


def test_dropped_is_counted_from_what_the_form_that_ran_computed(
        monkeypatch):
    """``dropped`` is the held assignments less the form's own count of
    what it computed (the buffer's live rows; the choices the every-expert
    mask let through): 0 in either form of ``held_moe``, and the size of
    the loss where the buffer is made to run past its rows (the choice of
    form overridden, which the layer itself never does)."""
    r = np.random.default_rng(1)
    T, d, E, k, f, held = 256, 16, 16, 4, 8, (0, 2)
    x = jnp.asarray(r.normal(size=(T, d)), jnp.float32)
    router = jnp.asarray(r.normal(size=(d, E)) * 0.3, jnp.float32)
    banks = [jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
             for s in ((2, d, f), (2, d, f), (2, f, d))]
    skew = jnp.zeros(E).at[:2].set(1.0)          # 2 x 256 held assignments
    run = lambda: jax.jit(lambda *a: moe.held_moe(*a, k, held, 2.5))(
        x, router, skew, *banks)
    out, route = run()
    assert int(route["rung"]) == 512 and int(route["dropped"]) == 0
    whole = np.asarray(out)
    forms = moe._held_forms
    monkeypatch.setattr(moe, "_held_forms",
                        lambda *a: [forms(*a)[0]] * 2)   # the buffer, always
    out, route = run()
    assert int(route["held_assignments"]) == 512
    assert int(route["dropped"]) == 512 - 256            # its 256 rows ran
    assert np.max(np.abs(np.asarray(out) - whole)) > 1e-3


def test_amp_reaches_the_held_experts_and_not_the_router():
    """``amp``: every grouped-matmul kernel of the step takes bf16
    operands; the router's three matmuls a layer stay float32 at the
    highest precision."""
    from test_olmoe import _eqns

    pt.seed(0)
    cfg = JoyaiConfig(**dict(SMALL, num_layers=2), held=(2, 2))
    model = Joyai(cfg)
    ids, labels = _batch(cfg, 2, 1)
    state = nn.get_state(model)
    opt = optimizer.SGD(learning_rate=1.0)
    step = make_train_step(model, opt, joyai_loss, donate=False, amp=True)
    jaxpr = jax.make_jaxpr(step)(state, opt.init(state["params"]),
                                 jax.random.key(0), (jnp.asarray(ids),),
                                 (jnp.asarray(labels),))
    kernels = [e for e in _eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert kernels
    for e in kernels:
        floats = [v.aval.dtype for v in e.invars
                  if jnp.issubdtype(v.aval.dtype, jnp.floating)]
        assert floats and all(d == jnp.bfloat16 for d in floats), e
    router = [e for e in _eqns(jaxpr.jaxpr)
              if e.primitive.name == "dot_general"
              and "HIGHEST" in str(e.params["precision"])]
    assert len(router) == 3 * cfg.expert_layers
    for e in router:
        assert all(v.aval.dtype == jnp.float32 for v in e.invars)
        assert any(cfg.num_experts in v.aval.shape for v in e.invars)


def test_rotary_pairs_closed_form_and_the_sources_deinterleave():
    """Pair (2i, 2i+1) of position t turns by t * theta^(-2i/D). The
    source de-interleaves (2i -> i, 2i+1 -> D/2+i) and rotates halves:
    that is this rotation followed by one fixed permutation, so q.k is the
    same for every pair of positions."""
    from paddle_tpu.models.transformer import rotary as rotate_half

    r = np.random.default_rng(0)
    D, theta = 8, 32000000.0
    x = jnp.asarray(r.normal(size=(1, 6, 2, D)), jnp.float32)
    y = np.asarray(rotary_pairs(x, theta))
    np.testing.assert_allclose(y[:, 0], np.asarray(x)[:, 0], atol=1e-7)
    np.testing.assert_allclose(np.linalg.norm(y, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    for t, i in ((5, 1), (3, 0), (4, 3)):
        ang = t * theta ** (-2.0 * i / D)
        a, b = float(x[0, t, 0, 2 * i]), float(x[0, t, 0, 2 * i + 1])
        assert abs(y[0, t, 0, 2 * i]
                   - (a * math.cos(ang) - b * math.sin(ang))) < 1e-5
        assert abs(y[0, t, 0, 2 * i + 1]
                   - (b * math.cos(ang) + a * math.sin(ang))) < 1e-5
    perm = np.concatenate([np.arange(0, D, 2), np.arange(1, D, 2)])
    source = lambda v: np.asarray(rotate_half(v[..., perm], theta))
    np.testing.assert_allclose(source(x), y[..., perm], atol=1e-6)
    k = jnp.asarray(r.normal(size=(1, 6, 2, D)), jnp.float32)
    ours = np.einsum("bqhd,bkhd->bhqk", y, np.asarray(rotary_pairs(k, theta)))
    theirs = np.einsum("bqhd,bkhd->bhqk", source(x), source(k))
    np.testing.assert_allclose(ours, theirs, atol=1e-5)


def test_prediction_loss_shift_and_mask():
    """``logits'[:, i]`` is scored against ``labels[:, i + 1]``; the last
    position has no label: whatever it holds changes nothing, and it gets
    no gradient. The loss is main + 0.3 x module."""
    r = np.random.default_rng(0)
    B, L, V = 2, 5, 7
    logits = jnp.asarray(r.normal(size=(B, L, V)), jnp.float32)
    ahead = jnp.asarray(r.normal(size=(B, L, V)), jnp.float32)
    labels = jnp.asarray(r.integers(0, V, (B, L)), jnp.int32)
    main, mtp = joyai_losses((logits, ahead), labels)
    logp = jax.nn.log_softmax(ahead, axis=-1)
    want = -np.mean([float(logp[b, i, labels[b, i + 1]])
                     for b in range(B) for i in range(L - 1)])
    assert abs(float(mtp) - want) < 1e-6
    logp = jax.nn.log_softmax(logits, axis=-1)
    want = -np.mean([float(logp[b, i, labels[b, i]])
                     for b in range(B) for i in range(L)])
    assert abs(float(main) - want) < 1e-6
    assert abs(float(joyai_loss((logits, ahead), labels))
               - float(main) - MTP_LOSS_WEIGHT * float(mtp)) < 1e-6
    other = ahead.at[:, -1].set(100.0)
    assert float(joyai_losses((logits, other), labels)[1]) == float(mtp)
    g = jax.grad(lambda a: joyai_loss((logits, a), labels))(ahead)
    assert float(jnp.max(jnp.abs(g[:, -1]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(g[:, :-1]), axis=-1))) > 0.0


def test_the_prediction_module_reads_the_next_tokens_embedding():
    """Changing ``ids[i + 1]`` changes ``logits'[i]`` and leaves the main
    model's ``logits[i]`` (causal) as they were."""
    pt.seed(2)
    cfg = JoyaiConfig(**SMALL, held=(0, 8))
    model = Joyai(cfg)
    ids, _ = _batch(cfg, 1, 3)
    other = ids.copy()
    other[0, 9] = (other[0, 9] + 1) % cfg.vocab_size
    a, a2 = model(jnp.asarray(ids))
    b, b2 = model(jnp.asarray(other))
    assert a.shape == a2.shape == (1, cfg.max_seq_len, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(a[0, :9]), np.asarray(b[0, :9]),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(a2[0, :8]), np.asarray(b2[0, :8]),
                               atol=1e-6)
    assert np.max(np.abs(np.asarray(a2[0, 8]) - np.asarray(b2[0, 8]))) > 1e-4


def test_flash_at_qk192_v128_matches_einsum_forward_and_backward():
    """The kernel path the block takes on the chip — causal, q.k at 192
    (padded to 256 lanes), P.v and the result at 128 (NOT padded to q's
    width) — in interpret mode, float32 operands, against einsum
    attention: output and all three gradients."""
    r = np.random.default_rng(0)
    B, L, H = 1, 256, 2
    q, k = (jnp.asarray(r.normal(size=(B, L, H, 192)) * 0.3, jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(r.normal(size=(B, L, H, 128)), jnp.float32)
            for _ in range(2))

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(192)
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True,
        precision="highest")
    out = flash(q, k, v)
    assert out.shape == (B, L, H, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    # what the kernels are handed: v and do 128 lanes wide, q and k 256
    from test_flash_attention import _kernel_operands

    ops = _kernel_operands(
        lambda q, k, v: jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, interpret=True)), argnums=(0, 1, 2))(q, k, v),
        q, k, v)
    wide, narrow = (B * H, L, 256), (B * H, L, 128)
    assert [s for _, s in ops["flash_fwd"]] == [wide, wide, narrow]
    assert [s for _, s in ops["flash_bwd_dkv"]] == [wide, wide, narrow,
                                                    narrow, narrow]


def test_attention_layer_flash_and_einsum_agree():
    """The latent-attention sublayer's two paths — the kernels (interpret
    mode, bf16 operands as on the chip) and the einsum — give the same
    output to bf16's rounding: the assembly of q, k (one rotary key for
    all heads) and v, and the 24 / 16 widths, are the kernel's too."""
    pt.seed(4)
    cfg = JoyaiConfig(**SMALL, held=(2, 2))
    attn = Joyai(cfg).blocks[0].attn
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 16, 32)),
                    jnp.float32)
    cfg.attn_impl = "einsum"
    a = np.asarray(attn(x))
    cfg.attn_impl = "flash"
    b = np.asarray(attn(x))
    assert a.shape == (2, 16, 32) and np.max(np.abs(a)) > 0
    assert np.max(np.abs(a - b)) <= 0.02 * np.max(np.abs(a))


def test_residual_init_scales_the_projections_into_the_stream():
    """W_o and every FFN's down matrix (dense, shared, the held banks)
    start at ``init_std / sqrt(2 * layers)``, the layers of the whole
    model where this is a slice of it; everything else at ``init_std``."""
    pt.seed(5)
    cfg = JoyaiConfig(**dict(SMALL, init_std=0.5, total_layers=1250),
                      held=(0, 8))
    assert cfg.out_std == 0.01
    for name, p in Joyai(cfg).named_parameters():
        if name.endswith("weight"):
            continue                      # norms: ones
        want = 0.01 if name.endswith(("w_o", "w_down")) else 0.5
        assert abs(float(jnp.std(p)) - want) < 0.15 * want, name
    small = JoyaiConfig(**SMALL)
    assert small.out_std == SMALL["init_std"] / (2 * small.num_layers) ** 0.5


@pytest.mark.parametrize("bad", [dict(n_group=8, topk_group=4),
                                 dict(n_group=1, topk_group=2),
                                 dict(held=(6, 4)), dict(num_mtp=2)])
def test_what_the_model_cannot_run_is_refused(bad):
    with pytest.raises(EnforceNotMet):
        Joyai(JoyaiConfig(**dict(SMALL, **bad)))


@pytest.mark.parametrize("option", [dict(rope_scaling=None), dict(hc_mult=1),
                                    dict(recompute="none"),
                                    dict(hc_sinkhorn_iters=3)],
                         ids=lambda o: next(iter(o)))
def test_the_new_options_at_rest_leave_the_step_its_jaxpr(option):
    """PR 51 gave ``JoyaiConfig`` a residual path, YaRN and recomputation
    as options: each at rest (and a Sinkhorn count that no one-stream model
    reads) traces JoyAI's train step to the jaxpr the defaults give,
    equation for equation — same state paths, same program."""
    def step_text(**kw):
        pt.seed(0)
        model = Joyai(JoyaiConfig(**SMALL, held=(2, 4), **kw))
        opt = optimizer.AdamW(1e-3, weight_decay=0.1)
        step = make_train_step(model, opt, joyai_loss, amp=True)
        state = nn.get_state(model)
        ids, labels = _batch(model.cfg, 2, 0)
        return list(state["params"]) + list(state["buffers"]), str(
            step.trace(state, opt.init(state["params"]), jax.random.key(0),
                       (jnp.asarray(ids),), (jnp.asarray(labels),)).jaxpr)

    assert step_text(**option) == step_text()


def test_a_recomputed_one_stream_step_is_the_plain_step():
    """``recompute: "blocks"`` on JoyAI itself (one stream, the prediction
    module outside the rebuilt blocks): loss, gradients, the routing record
    and the moved biases are the plain step's."""
    outs = []
    for recompute in ("none", "blocks"):
        pt.seed(2)
        model = Joyai(JoyaiConfig(**SMALL, held=(2, 4), recompute=recompute))
        ids, labels = _batch(model.cfg, 2, 5)
        loss, grads, buffers, _ = _sgd_step(model, ids, labels)
        outs.append((loss, grads, jax.device_get(dict(buffers))))
    (l0, g0, b0), (l1, g1, b1) = outs
    assert l0 == l1 and set(b0) == set(b1)
    for k in g0:
        np.testing.assert_allclose(g1[k], g0[k], rtol=0, atol=2e-7,
                                   err_msg=k)
    for k in b0:
        np.testing.assert_array_equal(b1[k], b0[k], err_msg=k)
    assert any(np.abs(b1[k]).max() > 0 for k in b1 if k.endswith(_BIAS))


def test_configuration_file_keeps_the_published_widths():
    """Every number of the catalog row's ``config`` under the same key;
    only depth, the experts held and the vocabulary are cut, with the
    published values and the deployment beside them."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    differs = {k for k, v in published.items() if cfg[k] != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {k: published[k] for k in differs}
    assert cfg["router_width"] == 256 and cfg["held_first"] == 0
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= 129280
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[cfg["name"]]
    assert set(entry["reduced"]) == differs
    # the parameter count the file states, from the shapes
    adapter = _load("_mla_adapter", "adapters", "causal_mla_moe_lm.py")
    pt.seed(0)      # a concrete ambient key: the trace below then leaves
    #                 none of its own in the process's stream
    shapes = jax.eval_shape(lambda: nn.get_state(
        Joyai(adapter._model_cfg(cfg)))["params"])
    n = sum(int(np.prod(s.shape)) for s in shapes.values())
    assert n == 680_439_808 and cfg["parameters"].startswith("680.4 M")


def test_compare_routing_tells_a_near_tie_from_a_wrong_choice():
    """Float32: a token whose 8th and 9th choice lie within ``gap`` may be
    resolved either way (counted, so that the caller compares with the
    reference GIVEN the system's index); a clear token that differs is a
    wrong router. And ``compare`` takes the losses it is given: a train
    step returns the weighted sum alone."""
    scores = np.full((1, 2, 4), 0.5)
    ref = {"own_index": np.asarray([[[0, 1], [2, 3]]]),
           "router_scores": scores, "gap": np.asarray([[1e-7, 0.1]])}
    got = {"expert_index": np.asarray([[[0, 2], [3, 2]]]),
           "router_scores": scores}
    out = REF.compare_routing(got, ref, "f32")
    assert out["ok"] and out["near_ties_resolved_differently"] == 1
    assert out["topk_match_where_clear"] == 1.0
    got["expert_index"] = np.asarray([[[0, 1], [2, 1]]])
    out = REF.compare_routing(got, ref, "f32")
    assert not out["ok"] and out["near_ties_resolved_differently"] == 0
    g = {"w": jnp.ones((2,))}
    step = {"total": 1.0, "grads": g}
    ref = {"loss": 2.0, "loss_mtp": 3.0, "total": 1.0, "grads": g}
    assert REF.compare(step, ref, "amp")["ok"]
    assert not REF.compare(dict(step, total=1.1), ref, "amp")["ok"]
    assert not REF.compare({"grads": g}, ref, "amp")["ok"]


def test_adamw_first_step_by_hand_and_compare_update():
    """The reference's AdamW from zero moments: m_hat = g, v_hat = g^2, so
    p <- p - lr (g / (|g| + eps) + decay p); ``compare_update`` passes the
    repo's own ``optimizer.AdamW`` step and fails a skipped update, a
    halved rate and a decay left out."""
    from paddle_tpu import optimizer

    hyper = {"lr": 4e-4, "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1}
    p = jnp.asarray([0.006, -0.006, 1.0, 0.5], jnp.float32)
    g = jnp.asarray([1e-5, -3e-9, 2e-4, 0.0], jnp.float32)
    want, v = REF.adamw_first_step(p, g, **hyper)
    by_hand = [0.006 - 4e-4 * (1.0 / (1.0 + 1e-3) + 6e-4),
               -0.006 - 4e-4 * (-3.0 / 13.0 - 6e-4),
               1.0 - 4e-4 * (1.0 + 0.1), 0.5 - 4e-4 * 0.05]
    np.testing.assert_allclose(np.asarray(want), by_hand, rtol=2e-7)
    np.testing.assert_allclose(np.asarray(v), 0.05 * np.asarray(g) ** 2,
                               rtol=1e-6)
    r = np.random.default_rng(3)
    before = {"w": jnp.asarray(r.normal(size=(64, 8)) * 0.006, jnp.float32),
              "norm.weight": jnp.ones((64,), jnp.float32)}
    grads = {k: jnp.asarray(r.normal(size=x.shape) * 1e-5, jnp.float32)
             for k, x in before.items()}

    def step(**kw):
        opt = optimizer.AdamW(**dict(dict(
            learning_rate=hyper["lr"], weight_decay=hyper["weight_decay"],
            beta1=0.9, beta2=0.95, epsilon=1e-8), **kw))
        after, state = opt.update(grads, opt.init(before), before)
        return REF.compare_update(
            jax.device_get(before), after, state["slots"]["m"],
            state["slots"]["v"], hyper)

    assert step()["ok"] and step()["param_rel"] <= 1e-5
    assert not step(learning_rate=2e-4)["ok"]
    assert not step(weight_decay=0.0)["ok"]
    same = REF.compare_update(before, before, grads, grads, hyper)
    assert not same["ok"] and same["param_rel"] > 0.99


def test_benchmark_flop_counts_by_hand():
    """``benchmarks/harness/flops_mla.py`` against the hand sum for the
    cell's configuration at L = 4096. Forward a token: the five latent
    projections 2 x (2048x1536 + 1536x6144 + 2048x576 + 512x8192 +
    4096x2048) = 52,690,944; causal scores and values 2 x 32 x (192 + 128)
    x 4097 / 2 = 41,953,280; router 2 x 2048 x 256 = 1,048,576; one expert
    3 x 2 x 2048 x 768 = 9,437,184 (the shared one; the held share of 8
    assignments 8 x 16 / 256 = half of one: 4,718,592); the dense FFN
    3 x 2 x 2048 x 7168 = 88,080,384; W_eh 2 x 4096 x 2048 = 16,777,216;
    a head 2 x 2048 x 16160 = 66,191,360."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        cfg = json.load(f)
    assert FLOPS.mla_projection_flops_per_token(cfg) == 52_690_944
    assert FLOPS.attention_core_flops_per_token(cfg, 4096) == 41_953_280
    attn = 52_690_944 + 41_953_280
    expert_block = attn + 1_048_576 + 9_437_184 + 4_718_592
    dense_block = attn + 88_080_384
    forward = dense_block + 5 * expert_block + 16_777_216 + 2 * 66_191_360
    assert forward == 881_127_424
    assert FLOPS.train_flops_per_token(cfg, 4096) == 3 * forward
    assert FLOPS.attention_blocks(cfg) == 6 and FLOPS.expert_blocks(cfg) == 5
    assert FLOPS.held_expert_flops(cfg, 2048) == 3 * 2048 * 9_437_184
    # the kernels at one 4096-token sequence, 32 heads: (query, key) pairs
    # the mask leaves 4096 x 4097 / 2 = 8,390,656 a head; forward 2 FLOP x
    # (192 + 128) a pair; bytes: q, k (192) and v (128) in bf16, o (128) in
    # float32, one float32 a row of statistics
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    pairs, rows = 8_390_656, 32 * 4096
    fwd = FLOPS.flash_kernel_floor("flash_fwd", cfg, 1, 4096, peaks)
    assert fwd["flop"] == 2 * 32 * pairs * 320 == 171_840_634_880
    assert fwd["bytes"] == rows * (512 * 2 + 128 * 4 + 4) == 201_850_880
    assert fwd["floor_s"] == fwd["flop"] / 197e12 > fwd["bytes"] / 819e9
    dq = FLOPS.flash_kernel_floor("flash_bwd_dq", cfg, 1, 4096, peaks)
    assert dq["flop"] == 2 * 32 * pairs * (2 * 192 + 128)
    assert dq["bytes"] == rows * ((512 + 128) * 2 + 8 + 192 * 4)
    dkv = FLOPS.flash_kernel_floor("flash_bwd_dkv", cfg, 1, 4096, peaks)
    assert dkv["flop"] == 2 * 32 * pairs * (2 * 192 + 2 * 128)
    assert dkv["bytes"] == rows * ((512 + 128) * 2 + 8 + 320 * 4)
    # the kernels run whole 512 x 512 blocks at 256 + 128 lanes: 36 of 64
    # blocks x 384 / 320 = 1.35 x the required FLOPs, so no share can pass
    # 1 / 1.35 = 0.74 of the peak
    assert (36 / 64 * 4096 ** 2 * 384) / (pairs * 320) == pytest.approx(
        1.35, abs=0.01)

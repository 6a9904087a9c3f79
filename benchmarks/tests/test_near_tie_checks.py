"""What ``correct`` excuses at a near-tie of a router, and what it refuses.

The OLMoE cell's check (``adapters/causal_moe_lm.check_reference`` against
``configs/olmoe-1b-7b.reference.py``) at the rehearsal's sizes, on the CPU:
a sound step whose ``top_k`` names the other expert of an exact tie is
correct (and is NOT under the comparison as it stood before PR 43, which
held the loss and every gradient leaf to the reference's own choice of the
two); each planted fault is refused by the limit named for it; and the
result line's ``reference.numbers`` hold every comparison's reading beside
its limit. Then the same excuse, host arithmetic alone, in the two other
references that have it.
"""

import functools
import importlib.util
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKLOAD = "olmoe_1b7b_seq4096"
#: the two experts whose router columns are made equal
TIED = (2, 5)
ROUTERS = ("blocks.0.moe.router_w", "blocks.1.moe.router_w")


def _module(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(seed=7):
    import jax
    from harness import spec

    cell = spec.Cell(spec.load_benchmark(), WORKLOAD, rehearse=True)
    system = cell.adapter().build(cell, seed, jax.devices()[:1], True,
                                  cell.generator(), {})
    return cell, system


def _set_routers(system, change, names=ROUTERS):
    params = dict(system.trainer.state["params"])
    for name in names:
        params[name] = change(params[name])
    system.trainer.state = dict(system.trainer.state, params=params)


def _tie(system):
    """Experts ``TIED`` of the first layer get one router column: their
    logits, and so their probabilities, are equal bit for bit for every
    token. At six times its size, so that the pair leads or trails most
    tokens' rankings and straddles the k-th place for a few only (3 of
    the check's 128 token-layers: the floor on clear tokens holds)."""
    a, b = TIED

    def change(w):
        col = w[:, a] * 6.0
        return w.at[:, a].set(col).at[:, b].set(col)

    _set_routers(system, change, ROUTERS[:1])


def _ties_to_the_higher_expert(monkeypatch):
    """The system's router with the other tie-break: the same top-k of the
    same probabilities, an exact tie resolved towards the HIGHER expert
    where ``lax.top_k`` (the reference's, and the system's own) takes the
    lower. What the chip's ``top_k`` did to one token of seed 1814549197."""
    from paddle_tpu.parallel import moe

    plain = moe.topk_route

    def route(logits, k):
        out = plain(logits[:, ::-1], k)
        return dict(out, index=logits.shape[-1] - 1 - out["index"],
                    counts=out["counts"][::-1])

    monkeypatch.setattr(moe, "topk_route", route)


def _planted(system, alter=None, labels=None):
    """Wrap the adapter's own step: ``alter`` changes what the float32 step
    hands to the comparison, ``labels`` what both steps train on. Returns
    the dict that will hold the float32 step's result as compared."""
    real, seen = system._step_and_routing, {}

    def step(amp, ids, lab):
        got = real(amp, ids, lab if labels is None else labels(lab))
        if not amp:
            if alter is not None:
                got = alter(got)
            seen["f32"] = got
        return got

    system._step_and_routing = step
    return seen


def _own(system, reference):
    """The reference on its own choice of experts, as HEAD compared."""
    ids, labels = system.check_items
    return reference.loss_and_grads(system.trainer.state["params"], ids,
                                    labels, system.cfg)


def _exact_tie(monkeypatch):
    cell, system = _build()
    reference = cell.reference()
    _tie(system)
    _ties_to_the_higher_expert(monkeypatch)
    seen = _planted(system)
    out = system.check_reference(reference)
    routing = out["f32_routing"]
    assert routing["near_ties_resolved_differently"] > 0
    assert routing["near_tie_excess"] == 0.0
    assert routing["topk_match_where_clear"] == 1.0
    assert out["f32"]["ok"] and routing["ok"], out
    assert out["f32"]["grad_leaf_rel"] < 1e-5 and out["f32"]["loss_rel"] < 1e-6
    # the fault PR 43 repaired, on record: held to the reference's OWN
    # choice of the two experts the same sound step is refused
    head = reference.compare(seen["f32"], _own(system, reference), "f32")
    assert not head["ok"] and head["grad_leaf_rel"] > 1e-4, head
    # the step as measured flips the same ties: its share of equal sets
    # falls by the tokens that straddle, which is what its limit is for
    assert out["amp"]["ok"], out["amp"]
    assert out["ok"] == out["amp_routing"]["ok"]


def _expert_at_clear_token(monkeypatch):
    cell, system = _build()
    reference = cell.reference()
    own = _own(system, reference)
    layer, token = np.unravel_index(np.argmax(own["gap"]), own["gap"].shape)

    def alter(got):
        index = np.array(got["expert_index"])
        row = index[layer, token]
        row[0] = next(e for e in range(system.cfg["num_experts"])
                      if e not in row)
        return dict(got, expert_index=index)

    _planted(system, alter)
    out = system.check_reference(reference)
    routing = out["f32_routing"]
    assert routing["topk_match_where_clear"] < 1.0
    assert routing["near_tie_excess"] == 0.0
    assert not routing["ok"] and not out["ok"]
    assert out["f32"]["ok"]         # the step itself was sound


def _expert_outside_the_tie(monkeypatch):
    cell, system = _build()
    reference = cell.reference()
    _tie(system)
    _ties_to_the_higher_expert(monkeypatch)
    own = _own(system, reference)
    gap = reference.TOL["f32"]["gap"]

    def alter(got):
        index = np.array(got["expert_index"])
        flipped = np.argwhere(
            (own["gap"][0] <= gap)
            & (np.sort(index[0], -1) != np.sort(own["own_index"][0], -1)
               ).any(-1))
        token = int(flipped[0, 0])
        row = index[0, token]
        # in place of the tied expert it took: the least probable of all
        row[list(row).index(TIED[1])] = int(
            np.argmin(own["router_probs"][0, token]))
        return dict(got, expert_index=index)

    _planted(system, alter)
    out = system.check_reference(reference)
    routing = out["f32_routing"]
    assert routing["near_tie_excess"] > 100 * gap
    assert routing["topk_match_where_clear"] == 1.0
    assert (routing["clear_tokens_share"]
            >= routing["tol"]["clear_tokens_share"])
    assert not routing["ok"] and not out["ok"]


@functools.lru_cache(maxsize=None)
def _leaf_zeroed_checks():
    import jax.numpy as jnp

    cell, system = _build()

    def alter(got):
        grads = dict(got["grads"])
        grads["blocks.0.moe.w_up"] = jnp.zeros_like(grads["blocks.0.moe.w_up"])
        return dict(got, grads=grads)

    _planted(system, alter)
    return system.check_reference(cell.reference())


def _leaf_zeroed(monkeypatch):
    out = _leaf_zeroed_checks()
    f32 = out["f32"]
    assert f32["worst_leaf"] == "blocks.0.moe.w_up"
    assert f32["grad_leaf_rel"] == 1.0 > f32["tol"]["grad_leaf_rel"]
    assert f32["loss_rel"] <= f32["tol"]["loss_rel"]
    assert not f32["ok"] and not out["ok"]
    assert out["f32_routing"]["ok"] and out["amp"]["ok"]


def _half_the_tokens(monkeypatch):
    cell, system = _build()

    def labels(lab):        # -100: ``cross_entropy`` leaves the position out
        lab = np.array(lab)
        lab[:, lab.shape[1] // 2:] = -100
        return lab

    _planted(system, labels=labels)
    out = system.check_reference(cell.reference())
    for mode in ("f32", "amp"):
        assert out[mode]["loss_rel"] > 10 * out[mode]["tol"]["loss_rel"]
        assert not out[mode]["ok"]
    assert out["f32_routing"]["ok"] and not out["ok"]


def _router_all_equal(monkeypatch):
    import jax.numpy as jnp

    cell, system = _build()
    _set_routers(system, jnp.zeros_like)
    out = system.check_reference(cell.reference())
    routing = out["f32_routing"]
    assert routing["clear_tokens_share"] == 0.0
    # every other limit holds: the floor alone refuses a comparison that
    # has no clear token left to compare
    assert routing["topk_match_where_clear"] == 1.0
    assert routing["near_tie_excess"] == 0.0
    assert routing["logits_abs"] <= routing["tol"]["logits_abs"]
    assert out["f32"]["ok"]
    assert not routing["ok"] and not out["ok"]


def _numbers(monkeypatch):
    """The last line's ``reference.numbers``, built by the function
    ``run.py`` calls, from the checks the zeroed leaf produced."""
    run = _module("_bench_run", "run.py")
    out = _leaf_zeroed_checks()
    numbers = run.compared(out)
    want = {f"{name}.{k}" for name in ("f32", "f32_routing", "amp_routing",
                                       "amp")
            for k in out[name]["tol"]}
    assert want == {k for k in numbers if not k.endswith(".ok")}
    assert want >= {"f32.loss_rel", "f32.grad_leaf_rel",
                    "f32_routing.logits_abs",
                    "f32_routing.clear_tokens_share",
                    "f32_routing.topk_match_where_clear",
                    "f32_routing.near_tie_excess", "amp_routing.topk_match",
                    "amp.loss_rel", "amp.grad_leaf_rel"}
    for name in ("f32", "f32_routing", "amp_routing", "amp"):
        for k, limit in out[name]["tol"].items():
            assert numbers[f"{name}.{k}"] == [out[name][k], limit]
        assert numbers[f"{name}.ok"] == [float(out[name]["ok"]), 1.0]
    reading, limit = numbers["f32.grad_leaf_rel"]
    assert reading == 1.0 and limit == 1e-4
    assert numbers["f32.ok"] == [0.0, 1.0]
    assert all(isinstance(x, float) for v in numbers.values() for x in v)


CASES = {"exact_tie": _exact_tie,
         "expert_at_clear_token": _expert_at_clear_token,
         "expert_outside_the_tie": _expert_outside_the_tie,
         "leaf_zeroed": _leaf_zeroed,
         "half_the_tokens": _half_the_tokens,
         "router_all_equal": _router_all_equal,
         "numbers": _numbers}


@pytest.mark.parametrize("case", list(CASES))
def test_olmoe_check(case, monkeypatch):
    CASES[case](monkeypatch)


@pytest.mark.parametrize("gap, ok", [(0.0, True), (1e-6, True),
                                     (5e-6, False)])
def test_olmoe_near_tie_excess_has_a_limit_under_the_gap(gap, ok):
    """The 9th expert in place of the 8th at a token that is not clear
    (``gap`` <= 1e-5: loss and gradients excuse it) reads the distance
    between the two, and is refused beyond ``TOL["f32"]["near_tie_excess"]``,
    which lies between the sound steps' readings on the chip (<= 1.5e-7)
    and the control's least (9.3e-6) — not at ``gap``, above that."""
    reference = _module("_ref_olmoe", "configs", "olmoe-1b-7b.reference.py")
    tol = reference.TOL["f32"]
    assert 1.5e-7 < tol["near_tie_excess"] < 9.3e-6 < tol["gap"]
    k, token = 8, 3
    probs = np.sort(np.random.default_rng(3).dirichlet(
        np.ones(64), (1, 64)), axis=-1)[..., ::-1].copy()
    probs[0, token, k] = probs[0, token, k - 1] - gap
    own = np.tile(np.arange(k), (1, 64, 1))
    index = own.copy()
    index[0, token, k - 1] = k
    logits = np.log(probs)
    out = reference.compare_routing(
        {"router_logits": logits, "expert_index": index},
        {"router_logits": logits, "router_probs": probs, "own_index": own,
         "gap": probs[..., k - 1] - probs[..., k]}, "f32")
    assert out["tol"]["near_tie_excess"] == tol["near_tie_excess"]
    assert out["near_ties_resolved_differently"] == 1
    assert out["topk_match_where_clear"] == 1.0 and out["logits_abs"] == 0.0
    assert out["near_tie_excess"] == pytest.approx(gap, abs=1e-12)
    assert out["ok"] is ok


# -- the same excuse in the two references that score by ``s + b`` -----------

@pytest.mark.parametrize("config", ["joyai-llm-flash", "lfm2-8b-a1b"])
@pytest.mark.parametrize("fault", ["none", "other_expert_of_the_tie",
                                   "expert_outside_the_tie"])
def test_near_tie_excess_by_score_and_bias(config, fault):
    """``compare_routing(..., "f32")`` on arrays made here: one token's
    k-th and (k+1)-th ``s + b`` tie exactly THROUGH the bias (the scores
    differ, their sums do not); the system may take either, and nothing
    else."""
    reference = _module("_ref_" + config.replace("-", "_"), "configs",
                        config + ".reference.py")
    k, token = 4, 9
    scores = np.random.default_rng(5).uniform(0.3, 0.7, (1, 64, 16))
    bias = np.zeros((1, scores.shape[-1]))
    order = np.argsort(-scores[0, token])
    kth, nxt, last = order[k - 1], order[k], order[-1]
    bias[0, nxt] = scores[0, token, kth] - scores[0, token, nxt]
    select = scores + bias[:, None, :]
    ranked = np.sort(select, axis=-1)[..., ::-1]
    own = np.argsort(-select, axis=-1, kind="stable")[..., :k]
    own[0, token][own[0, token] == nxt] = kth      # of the tie: this one
    ref = {"router_scores": scores, "bias": bias, "own_index": own,
           "gap": ranked[..., k - 1] - ranked[..., k]}
    assert ref["gap"][0, token] <= 1e-12 and kth in own[0, token]
    index = own.copy()
    if fault != "none":
        index[0, token][list(index[0, token]).index(kth)] = (
            nxt if fault == "other_expert_of_the_tie" else last)
    out = reference.compare_routing(
        {"router_scores": scores, "expert_index": index}, ref, "f32")
    gap = reference.TOL["f32"]["gap"]
    assert out["tol"]["near_tie_excess"] == gap
    assert out["ranked_by"] == "s + b"
    assert out["topk_match_where_clear"] == 1.0
    assert out["near_ties_resolved_differently"] == int(fault != "none")
    if fault == "expert_outside_the_tie":
        assert out["near_tie_excess"] > 1000 * gap and not out["ok"]
    else:
        assert out["near_tie_excess"] <= 1e-12 and out["ok"]


def test_numbers_of_a_reading_that_is_not_finite_stay_json():
    """A loss that is NaN is a reason to refuse, and has to reach the
    record: the line's last key may not make the line unreadable."""
    import json

    run = _module("_bench_run", "run.py")
    numbers = run.compared({
        "ok": False, "loss_rel": float("nan"), "tol": {"loss_rel": 3e-5},
        "configured_step": {"ok": False, "rows_upd_rel": {"w": float("inf")},
                            "tol": {"rows_upd_rel": {"w": 0.15, "v": 0.1}}}})
    assert numbers == {"loss_rel": ["nan", 3e-5],
                       "configured_step.ok": [0.0, 1.0],
                       "configured_step.rows_upd_rel.w": ["inf", 0.15]}
    json.loads(json.dumps(numbers), parse_constant=lambda name: 1 / 0)

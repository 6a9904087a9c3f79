"""The per-row CTR update (all rules): the ONE definition of the rule.

The reference applies its sparse optimizer on-device inside the
hashtable update kernels (`/root/reference/paddle/fluid/framework/fleet/
heter_ps/optimizer.cuh.h:27-100` — update_lr/update_mf/update_value with
show/click coeffs, bounds, lazy mf creation), one GPU thread per row;
the CPU server supports the full rule family (sparse_sgd_rule.h:27-135:
naive / AdaGrad shared-g2sum / StdAdaGrad per-dim / Adam). Here the
random-access gather/scatter and the per-row math between them are all
XLA: ``fused_row_update`` is plain jnp on gathered (or whole-table)
columns, and both push formulations of ``ps.embedding_cache`` call it.
All four reference rules are supported for both the embed (1-d) and
embedx (dim-d) blocks; the host oracle is ``ps/sgd_rule.py`` through
``MemorySparseTable`` (tests/test_sparse_optimizer.py).

There is no kernel. A Pallas launcher of this rule compiled for the v5e
and lost: 20.7 against 19.5 ms a push at 2^26 rows x 106,496 slots
(PR 25, PERF.md section 6) — its ``[n, 1]`` operands pad to 128 lanes,
and XLA already fuses the rule into the gathers' consumers.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

__all__ = ["fused_row_update", "rule_update", "rule_state_dim",
           "rule_init_state"]


def rule_state_dim(rule: str, dim: int) -> int:
    """Optimizer-state floats per feature (sparse_sgd_rule slot dims)."""
    return {"naive": 0, "adagrad": 1, "std_adagrad": dim,
            "adam": 2 * dim + 2}[rule]


def rule_init_state(rule: str, n: int, dim: int, *, beta1: float,
                    beta2: float):
    """Fresh-feature optimizer state (zeros; Adam's beta powers start at
    beta1/beta2 — sparse_sgd_rule.cc InitValueWork)."""
    if rule == "adam":
        return jnp.concatenate(
            [jnp.zeros((n, 2 * dim), jnp.float32),
             jnp.full((n, 1), beta1, jnp.float32),
             jnp.full((n, 1), beta2, jnp.float32)], axis=1)
    return jnp.zeros((n, rule_state_dim(rule, dim)), jnp.float32)


def _m32(a, b):
    """f32 multiply with PINNED operand binding and IEEE rounding.

    The sparse rules must produce the same bits as the host engines
    (csrc builds with -ffp-contract=off; numpy never contracts) — the
    hot embedding tier round-trips rows between them. Two XLA behaviors
    break that on a plain ``a * b`` chain:

    - LLVM contracts a single-use `mul` feeding an `add`/`sub` into one
      FMA (no intermediate rounding);
    - the HLO algebraic simplifier re-associates scalar-constant mul
      chains (``lr*sg*ratio`` becomes ``sg*(lr*ratio)`` — the constant
      sinks onto the narrower broadcast operand).

    Every pure seal was tried and folded away (optimization_barrier,
    reduce_precision(8,23), bitcast pairs, min/max(±inf), +0.0); what
    holds is making the product MULTI-USE via ``t + 0*t``: LLVM only
    forms fmuladd from a single-use mul, XLA keeps ``0*x`` under strict
    inf/nan semantics, and the add consumer breaks the mul-chain pattern
    the re-associator matches on. Cost: one extra fused mul+add per
    element. Known edge: t=±inf becomes NaN here (0·inf) — already
    -diverged training only, and the nan/inf guard surfaces it anyway."""
    t = a * b
    return t + jnp.float32(0.0) * t


def rule_update(rule: str, w, state, g, scale, *, lr, initial_g2sum,
                wmin, wmax, beta1, beta2, eps):
    """One batched rule step on touched rows: (w [n,d], state [n,sd],
    g [n,d] merged grads, scale [n,1] push_show) -> (w', state').
    Mirrors this repo's host rules (ps/sgd_rule.py) exactly — which
    follow sparse_sgd_rule.cc (SURVEY Appendix A.2) except that Adam
    adds epsilon to the bias-corrected sqrt(v_hat) rather than the
    reference's raw sqrt(v) (an eps-placement difference only). Adam
    ignores the scale like the reference."""
    clip = lambda x: jnp.clip(x, wmin, wmax)
    lrf = jnp.float32(lr)
    if rule == "naive":
        return clip(w - _m32(lrf, g)), state
    if rule == "adagrad":  # one shared g2sum per feature
        sg = g / scale
        ratio = jnp.sqrt(initial_g2sum / (initial_g2sum + state))
        w2 = clip(w - _m32(_m32(lrf, sg), ratio))
        # g2sum accumulates in the native table's association (sequential
        # over dims, ONE divide at the end — sparse_table.h kRuleAdaGrad);
        # jnp.mean's tree reduce re-associates the f32 sum and breaks
        # bit-parity with the host/PS rows the hot tier must round-trip
        add = _m32(sg[:, 0], sg[:, 0])
        for i in range(1, g.shape[1]):
            add = add + _m32(sg[:, i], sg[:, i])
        return w2, state + (add / jnp.float32(g.shape[1]))[:, None]
    if rule == "std_adagrad":  # per-dim g2sum
        sg = g / scale
        ratio = jnp.sqrt(initial_g2sum / (initial_g2sum + state))
        return (clip(w - _m32(_m32(lrf, sg), ratio)), state + _m32(sg, sg))
    if rule == "adam":
        d = w.shape[1]
        m, v = state[:, :d], state[:, d:2 * d]
        b1p, b2p = state[:, 2 * d:2 * d + 1], state[:, 2 * d + 1:2 * d + 2]
        # (1 - beta) must round through f32 like the native rule's
        # `1.0f - cfg.beta1` — the python-double difference (1e-8 on
        # beta1=0.9) compounds into m/v and breaks row bit-parity
        b1f, b2f = jnp.float32(beta1), jnp.float32(beta2)
        one = jnp.float32(1.0)
        m2 = _m32(b1f, m) + _m32(one - b1f, g)
        v2 = _m32(b2f, v) + _m32(_m32(one - b2f, g), g)
        m_hat = m2 / (one - b1p)
        v_hat = v2 / (one - b2p)
        w2 = clip(w - _m32(lrf, m_hat) / (jnp.sqrt(v_hat) + eps))
        return w2, jnp.concatenate(
            [m2, v2, _m32(b1p, b1f), _m32(b2p, b2f)], axis=1)
    raise KeyError(f"unknown sparse sgd rule {rule!r}")


def fused_row_update(show, click, ew, estate, xw, xstate, has,
                     dshow, dclick, ge, gx,
                     *, embed_rule, embedx_rule, dim, lr, initial_g2sum,
                     wmin, wmax, beta1, beta2, eps, nonclk_coeff,
                     click_coeff, embedx_threshold, create_applies_grad):
    """The complete per-row CTR update on plain arrays (touched rows,
    pre-merged): show/click accumulation, embed rule step, lazy embedx
    creation, embedx rule step. ONE definition shared by the touched
    -rows push and the sweep (``ps.embedding_cache``) — divergence
    between the two formulations is structurally impossible. Returns the
    seven updated columns; a stateless rule's state column is zero wide
    and round-trips unchanged."""
    upd = functools.partial(rule_update, lr=lr, initial_g2sum=initial_g2sum,
                            wmin=wmin, wmax=wmax, beta1=beta1, beta2=beta2,
                            eps=eps)
    # [n] bool -> [n, 1] through f32 + compare: the form the cells'
    # compiled steps hold (PR 28 pinned them byte for byte; a plain
    # ``m[:, None]`` compiles to a different program)
    col = lambda m: m.astype(jnp.float32)[:, None] > 0.5

    show_new = show + dshow
    click_new = click + dclick
    scale = jnp.maximum(dshow, 1e-10)[:, None]

    ew_new, es_new = upd(embed_rule, ew, estate, ge, scale)

    # lazy embedx creation on the show/click score: created rows start
    # from INIT state; create_applies_grad selects CPU (create + apply,
    # ctr_accessor.cc order) vs GPU (create only, optimizer.cuh.h:81-94)
    # the host computes this over totals too (pstpu::show_click_score);
    # both products sealed so the create-threshold compare sees the same
    # bits as the PS and creation fires on the same push
    score = (_m32(show_new - click_new, jnp.float32(nonclk_coeff))
             + _m32(click_new, jnp.float32(click_coeff)))
    had = has > 0
    create = jnp.logical_and(jnp.logical_not(had),
                             score >= embedx_threshold)
    apply_mask = jnp.logical_or(had, create) if create_applies_grad else had
    init = rule_init_state(embedx_rule, show.shape[0], dim, beta1=beta1,
                           beta2=beta2)
    st_base = jnp.where(col(create), init, xstate)
    xw_new, xs_new = upd(embedx_rule, xw, st_base, gx, scale)

    return (show_new, click_new, ew_new, es_new,
            jnp.where(col(apply_mask), xw_new, xw),
            jnp.where(col(apply_mask), xs_new, st_base),
            jnp.where(create, 1.0, has))

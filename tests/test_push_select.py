"""The sparse push's two formulations: which one ``push_mode="auto"``
picks for which shapes (``resolve_push_mode``, keyed on the crossover the
v5e measured, PERF.md §5), where the choice is recorded
(``pt.push.select``), and that the touched-rows path agrees with the
sweep and with the merge it replaced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import profiler
from paddle_tpu.ops.sparse_optimizer import rule_state_dim
from paddle_tpu.ps import embedding_cache as ec
from paddle_tpu.ps import sharded_cache as sc
from paddle_tpu.ps.embedding_cache import (CacheConfig, cache_push,
                                           merge_sparse_grads,
                                           resolve_push_mode)

RULES = ["naive", "adagrad", "std_adagrad", "adam"]
PASS_SLOTS = 4096 * 26                       # deepfm_pass_zipf's batch
ROUTED_SLOTS = 4 * sc.route_bucket_capacity(PASS_SLOTS, 4)   # 213,024


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("capacity,slots,want", [
    # v5e, PR 25 (PERF.md section 5; ms a push, sweep / touched rows with
    # the cells' Zipf repeats / touched rows with no row named twice)
    (1 << 26, PASS_SLOTS, "sparse"),     # the pass cell, 630 rows a slot: 85.4 / 19.5 / 28.0
    (1 << 25, ROUTED_SLOTS, "sparse"),   # the routed cell's shard, 157: 59.5 / 23.8 / 32.7
    (1 << 25, PASS_SLOTS, "sparse"),     # 315: 47.9 / 17.5 / 26.0
    (1 << 23, PASS_SLOTS, "dense"),      # 79: 19.8 / 13.7 / 23.8 — the worst case decides
    (1 << 21, PASS_SLOTS, "dense"),      # chip_smoke, the hot tier, 20: 12.2 / 10.3 / 16.3
    (1 << 21, ROUTED_SLOTS, "dense"),    # 10: 24.9 / 18.0 / 27.0
])
def test_auto_on_tpu_is_keyed_on_the_shapes(as_tpu, capacity, slots, want):
    assert ROUTED_SLOTS == 213024
    assert resolve_push_mode("auto", capacity, slots) == want
    # the boundary is ONE number of rows per slot
    R = ec.SWEEP_MAX_ROWS_PER_SLOT
    assert resolve_push_mode("auto", R * slots - 1, slots) == "dense"
    assert resolve_push_mode("auto", R * slots, slots) == "sparse"


@pytest.mark.parametrize("capacity,slots", [(1 << 26, PASS_SLOTS),
                                            (1 << 12, PASS_SLOTS)])
def test_auto_off_tpu_is_sparse_and_named_modes_mean_themselves(
        capacity, slots, monkeypatch):
    assert resolve_push_mode("auto", capacity, slots) == "sparse"
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert resolve_push_mode("dense", capacity, slots) == "dense"
        assert resolve_push_mode("sparse", capacity, slots) == "sparse"


def test_select_routing_hands_the_push_its_shapes(as_tpu, monkeypatch):
    """``select_routing`` resolves ``auto`` for the shard's rows and the
    routed push's received slots, not for the backend alone."""
    seen = []
    real = ec.resolve_push_mode
    monkeypatch.setattr(sc, "resolve_push_mode",
                        lambda *a: seen.append(a) or real(*a))
    assert sc.select_routing(PASS_SLOTS, 1 << 25, 4, "auto") == (
        "alltoall", "alltoall")
    assert seen == [("auto", 1 << 25, ROUTED_SLOTS)]


def _state(rng, C, dim, rule):
    es, xs = rule_state_dim(rule, 1), rule_state_dim(rule, dim)
    f32 = lambda a: jnp.asarray(np.asarray(a, np.float32))
    st = {"show": f32(rng.integers(0, 5, C)),
          "click": f32(rng.integers(0, 2, C)),
          "embed_w": f32(rng.normal(size=(C, 1))),
          "embed_state": f32(rng.uniform(0, 1, (C, es))),
          "embedx_w": f32(rng.normal(size=(C, dim))),
          "embedx_state": f32(rng.uniform(0, 1, (C, xs))),
          "has_embedx": f32(rng.random(C) < 0.5)}
    if rule == "adam":
        st["embed_state"] = st["embed_state"].at[:, -2:].set(0.9)
        st["embedx_state"] = st["embedx_state"].at[:, -2:].set(0.9)
    return st


def _batch(rng, C, n, kind):
    rows = rng.integers(0, 48, n).astype(np.int32)     # heavy repeats
    rows[:6] = C - 1                                   # the last row
    rows[-30:] = C                                     # the sentinel
    rows[40:44] = -1                                   # a raw miss marker
    rows[10:20] = 100                                  # only at show = 0
    if kind == "all_padding":
        rows[:] = C
    grads = rng.normal(size=(n, 5)).astype(np.float32)
    shows = np.ones(n, np.float32)
    shows[10:20], grads[10:20] = 0.0, 0.0
    clicks = (rng.random(n) < 0.4).astype(np.float32) * shows
    return tuple(map(jnp.asarray, (rows, grads, shows, clicks)))


@pytest.mark.parametrize("kind", ["mixed", "all_padding", "chunked"])
@pytest.mark.parametrize("rule", RULES)
def test_touched_rows_match_the_sweep(rng, rule, kind, monkeypatch):
    """Same pushes through both formulations: counts and ``has_embedx``
    equal, weights and optimizer state within 1e-6, rows the batch did
    not name bit-equal to where they started — with repeats, the
    sentinel, a raw miss marker, row C-1, a row whose occurrences all
    carry show 0 (the rule still runs there), a batch of padding, and
    the batch walked in chunks (the last one part padding)."""
    C, dim, n = 256, 4, 300
    if kind == "chunked":
        monkeypatch.setattr(ec, "PUSH_CHUNK", 16)
    state = _state(rng, C, dim, rule)
    batch = _batch(rng, C, n, kind)
    kw = dict(capacity=C, embedx_dim=dim, embedx_threshold=2.0,
              embed_rule=rule, embedx_rule=rule)
    out = {}
    for mode in ("dense", "sparse"):
        cfg = CacheConfig(push_mode=mode, **kw)
        out[mode] = jax.jit(lambda st, *b: cache_push(st, *b, cfg))(
            state, *batch)
    rows = np.asarray(batch[0])
    named = np.zeros(C, bool)
    named[rows[(rows >= 0) & (rows < C)]] = True
    assert named.any() == (kind != "all_padding")
    if kind == "chunked":
        assert 32 < named.sum() < 64    # several chunks of 16, one partial
    for k, start in state.items():
        sweep, touched = np.asarray(out["dense"][k]), np.asarray(out["sparse"][k])
        if k in ("show", "click", "has_embedx"):
            np.testing.assert_array_equal(touched, sweep, err_msg=k)
        else:
            np.testing.assert_allclose(touched, sweep, rtol=0, atol=1e-6,
                                       err_msg=k)
        for got in (sweep, touched):
            np.testing.assert_array_equal(got[~named],
                                          np.asarray(start)[~named],
                                          err_msg=f"unnamed rows of {k}")
    if kind != "all_padding":
        # exact counts: every occurrence with a show lands once
        want = np.asarray(state["show"]).copy()
        np.add.at(want, rows[(rows >= 0) & (rows < C)],
                  np.asarray(batch[2])[(rows >= 0) & (rows < C)])
        np.testing.assert_array_equal(np.asarray(out["sparse"]["show"]), want)
        if rule == "adam":   # the rule ran on row 100 at zero delta
            assert not np.array_equal(
                np.asarray(out["sparse"]["embed_state"])[100],
                np.asarray(state["embed_state"])[100])


@pytest.mark.parametrize("n", [1, 7, 300])
def test_merge_keeps_the_association_of_unique_plus_segment_sum(rng, n):
    """The one sort + packed segment-sum gives the bits of the merge it
    replaced (``jnp.unique`` + three ``segment_sum``s over its inverse):
    the f32 association is part of the host-parity contract."""
    C = 64
    rows = rng.integers(0, 12, n).astype(np.int32)
    rows[::5] = C
    grads = rng.normal(size=(n, 5)).astype(np.float32)
    shows = rng.uniform(0, 2, n).astype(np.float32)
    clicks = rng.uniform(0, 1, n).astype(np.float32)
    uniq, s, c, g = jax.jit(merge_sparse_grads, static_argnums=4)(
        rows, grads, shows, clicks, C)
    u_ref, inv = jnp.unique(rows, size=n, fill_value=C, return_inverse=True)
    seg = lambda x: jax.ops.segment_sum(x, inv.reshape(-1), num_segments=n)
    np.testing.assert_array_equal(uniq, u_ref)
    for got, ref in ((s, seg(shows)), (c, seg(clicks)), (g, seg(grads))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_push_select_is_recorded_once_a_compile(as_tpu):
    """The choice is static per compiled shape: one ``pt.push.select``
    span with the shapes and the choice per trace, none per step."""
    C, dim = 1 << 12, 4
    cfg = CacheConfig(capacity=C, embedx_dim=dim, embedx_threshold=0.0)
    step = jax.jit(lambda st, *b: cache_push(st, *b, cfg))
    rng = np.random.default_rng(0)
    selects = lambda: [s.counts for s in profiler.host_spans()
                       if s.name == "pt.push.select"]
    R = ec.SWEEP_MAX_ROWS_PER_SLOT
    for n, sweep in ((C // R, 0), (C // R + 1, 1)):
        state = _state(rng, C, dim, "adagrad")
        batch = (jnp.asarray(rng.integers(0, C, n), jnp.int32),
                 jnp.zeros((n, 1 + dim)), jnp.ones(n), jnp.zeros(n))
        before = len(selects())
        for _ in range(3):
            state = step(state, *batch)
        new = selects()[before:]
        assert new == [{"capacity": C, "rows": n, "sweep": sweep}], new

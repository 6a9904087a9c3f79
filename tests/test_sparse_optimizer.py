"""The per-row CTR rule on the device (ops/sparse_optimizer.py through
``cache_push``) against the HOST rules — ``MemorySparseTable`` and
``ps/sgd_rule`` — across the whole rule family, both lazy-embedx create
orders and the padded last chunk of the touched-rows walk; and the sweep
against the touched rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.sparse_optimizer import rule_state_dim
from paddle_tpu.ps import embedding_cache
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.embedding_cache import CacheConfig, cache_push
from paddle_tpu.ps.sgd_rule import SGDRuleConfig, make_sgd_rule
from paddle_tpu.ps.table import MemorySparseTable, TableConfig

RULES = ["naive", "adagrad", "std_adagrad", "adam"]
PAIRS = [(r, r) for r in RULES] + [("adagrad", "adam"),
                                   ("naive", "std_adagrad")]
_COLS = ("show", "click", "embed_w", "embed_state", "embedx_w",
         "embedx_state", "has_embedx")


def _state(rng, C, dim, embed_rule="adagrad", embedx_rule="adagrad"):
    es = rule_state_dim(embed_rule, 1)
    xs = rule_state_dim(embedx_rule, dim)
    st = {
        "show": jnp.asarray(rng.uniform(0, 5, C).astype(np.float32)),
        "click": jnp.asarray(rng.uniform(0, 2, C).astype(np.float32)),
        "embed_w": jnp.asarray(rng.normal(size=(C, 1)).astype(np.float32)),
        "embed_state": jnp.asarray(rng.uniform(0, 1, (C, es)).astype(np.float32)),
        "embedx_w": jnp.asarray(rng.normal(size=(C, dim)).astype(np.float32)),
        "embedx_state": jnp.asarray(rng.uniform(0, 1, (C, xs)).astype(np.float32)),
        "has_embedx": jnp.asarray((rng.random(C) < 0.5).astype(np.float32)),
    }
    if embed_rule == "adam" and es:
        st["embed_state"] = st["embed_state"].at[:, -2:].set(0.9)
    if embedx_rule == "adam" and xs:
        st["embedx_state"] = st["embedx_state"].at[:, -2:].set(0.9)
    return st


# ---------------------------------------------------------------------------
# the touched-rows push against the host rules. Neither oracle below
# reaches ops/sparse_optimizer: the CPU create order is the host table
# itself, the GPU order a numpy loop over ps/sgd_rule's rules.
# ---------------------------------------------------------------------------

_THRESHOLD = 3.0


def _host_state(rng, C, dim, embed_rule, embedx_rule):
    """A trained-looking table a host table can hold too: rows without
    an embedx block have none of its weights or state (lazy creation
    starts both from the rule's init)."""
    st = {k: np.asarray(v) for k, v in
          _state(rng, C, dim, embed_rule, embedx_rule).items()}
    none = st["has_embedx"] == 0
    st["embedx_w"] = np.where(none[:, None], 0.0, st["embedx_w"])
    st["embedx_state"] = np.where(none[:, None], 0.0, st["embedx_state"])
    return {k: v.astype(np.float32) for k, v in st.items()}


def _accessor(dim, embed_rule, embedx_rule):
    return AccessorConfig(embedx_dim=dim, embedx_threshold=_THRESHOLD,
                          embed_sgd_rule=embed_rule,
                          embedx_sgd_rule=embedx_rule,
                          sgd=SGDRuleConfig(initial_range=0.0))


def _full_rows(st):
    """The host table's save layout: slot, unseen_days, delta_score, show,
    click, embed_w, embed_state, has_embedx, embedx_w, embedx_state."""
    z = np.zeros((len(st["show"]), 3), np.float32)
    return np.concatenate(
        [z, st["show"][:, None], st["click"][:, None], st["embed_w"],
         st["embed_state"], st["has_embedx"][:, None], st["embedx_w"],
         st["embedx_state"]], axis=1)


def _table_push(st, acc, rows, grads, shows, clicks):
    """CPU create order (create the block, then apply the creating push):
    ``MemorySparseTable.push_sparse`` on a table holding ``st``."""
    C = len(st["show"])
    keys = np.arange(1, C + 1, dtype=np.uint64)
    table = MemorySparseTable(TableConfig(shard_num=2, accessor_config=acc))
    table.import_full(keys, _full_rows(st))
    push = np.concatenate([np.zeros((len(rows), 1), np.float32),
                           shows[:, None], clicks[:, None], grads], axis=1)
    table.push_sparse(keys[rows], push)
    full, found = table.export_full(keys)
    assert found.all()
    es = st["embed_state"].shape[1]
    xd = st["embedx_w"].shape[1]
    return {"show": full[:, 3], "click": full[:, 4], "embed_w": full[:, 5:6],
            "embed_state": full[:, 6:6 + es], "has_embedx": full[:, 6 + es],
            "embedx_w": full[:, 7 + es:7 + es + xd],
            "embedx_state": full[:, 7 + es + xd:]}


def _loop_push(st, acc, rows, grads, shows, clicks, create_applies_grad):
    """Row by row over ``ps/sgd_rule``'s host rules: occurrences of a row
    summed in batch order, show/click accumulated, the embed rule, lazy
    creation on the score of the totals, the embedx rule where the block
    exists — and, in the GPU order, NOT on the push that created it."""
    st = {k: v.copy() for k, v in st.items()}
    dim = st["embedx_w"].shape[1]
    embed = make_sgd_rule(acc.embed_sgd_rule, 1, acc.sgd)
    embedx = make_sgd_rule(acc.embedx_sgd_rule, dim, acc.sgd)
    nothing = np.random.default_rng(0)   # initial_range 0: draws only zeros
    for r in np.unique(rows):
        at = np.flatnonzero(rows == r)
        g = np.zeros((1, 1 + dim), np.float32)
        dshow = dclick = np.float32(0.0)
        for i in at:
            g += grads[i]
            dshow += shows[i]
            dclick += clicks[i]
        scale = np.asarray([dshow], np.float32)
        st["show"][r] += dshow
        st["click"][r] += dclick
        w, s = st["embed_w"][r:r + 1], st["embed_state"][r:r + 1]
        embed.update(w, s, g[:, :1], scale)
        score = ((st["show"][r] - st["click"][r]) * np.float32(acc.nonclk_coeff)
                 + st["click"][r] * np.float32(acc.click_coeff))
        created = st["has_embedx"][r] == 0 and score >= acc.embedx_threshold
        if created:
            xw, xs = embedx.init_value(1, nothing)
            st["embedx_w"][r], st["embedx_state"][r] = xw[0], xs[0]
            st["has_embedx"][r] = 1.0
        if st["has_embedx"][r] and (create_applies_grad or not created):
            xw, xs = st["embedx_w"][r:r + 1], st["embedx_state"][r:r + 1]
            embedx.update(xw, xs, g[:, 1:], scale)
    return st


def _assert_rows(got, want, what):
    # bit for bit: rows round-trip between the device and the host
    # engines (the hot tier), so the rule's bits are part of the contract
    for k in _COLS:
        np.testing.assert_array_equal(np.asarray(got[k]), want[k],
                                      err_msg=f"{what}: {k}")


def _touched_rows_against_host(rng, monkeypatch, embed_rule, embedx_rule,
                               create_applies_grad, n):
    # five chunks of 64 slots at n = 300, the last one padded
    monkeypatch.setattr(embedding_cache, "PUSH_CHUNK", 64)
    C, dim = 512, 4
    st = _host_state(rng, C, dim, embed_rule, embedx_rule)
    rows = rng.integers(0, C, n).astype(np.int32)
    rows[n // 2:] = rows[:n - n // 2]               # every other row twice
    grads = rng.normal(size=(n, 1 + dim)).astype(np.float32)
    shows = np.ones(n, np.float32)
    clicks = (rng.random(n) < 0.4).astype(np.float32)
    acc = _accessor(dim, embed_rule, embedx_rule)
    cfg = CacheConfig(capacity=C, embedx_dim=dim, embedx_threshold=_THRESHOLD,
                      embed_rule=embed_rule, embedx_rule=embedx_rule,
                      create_applies_grad=create_applies_grad,
                      push_mode="sparse")
    # a sentinel slot (a missing key) rides along and must drop
    pad = lambda a, v: jnp.asarray(np.concatenate(
        [a, np.full((1,) + a.shape[1:], v, a.dtype)]))
    got = jax.jit(lambda s: cache_push(
        s, pad(rows, C), pad(grads, 7.0), pad(shows, 1.0), pad(clicks, 1.0),
        cfg))({k: jnp.asarray(v) for k, v in st.items()})
    created = (np.asarray(got["has_embedx"]) != st["has_embedx"]).sum()
    loop = _loop_push(st, acc, rows, grads, shows, clicks,
                      create_applies_grad)
    if create_applies_grad:
        table = _table_push(st, acc, rows, grads, shows, clicks)
        _assert_rows(got, table, "device against the host table")
        # ... and the loop is the table's order with one flag turned
        _assert_rows(loop, table, "numpy loop against the host table")
    else:
        _assert_rows(got, loop, "device against the numpy loop")
    return created


@pytest.mark.parametrize("create_applies_grad", [True, False],
                         ids=["cpu_order", "gpu_order"])
@pytest.mark.parametrize("embed_rule,embedx_rule", PAIRS,
                         ids=["+".join(p) for p in PAIRS])
def test_touched_rows_match_host_rules(rng, monkeypatch, embed_rule,
                                       embedx_rule, create_applies_grad):
    """``cache_push`` on the touched rows == the host rules for the same
    batch, every rule pair, both create orders (CPU: the host table; GPU:
    the numpy loop, which in the CPU order is held to the table too)."""
    created = _touched_rows_against_host(
        rng, monkeypatch, embed_rule, embedx_rule, create_applies_grad, 300)
    assert created > 0      # the orders differ only on a creating push


@pytest.mark.parametrize("n", [1, 129])
def test_touched_rows_padded_last_chunk_matches_host(rng, monkeypatch, n):
    """One slot (a chunk of one) and 129 + the sentinel (two chunks of 64
    and a padded third) against the host table."""
    _touched_rows_against_host(rng, monkeypatch, "adagrad", "adagrad", True,
                               n)


@pytest.mark.parametrize("rule", RULES)
def test_cache_push_matches_host_table(rng, rule):
    """Device cache push == host MemorySparseTable push for the same
    merged records, for every rule (the parity-critical A.2 math)."""
    from paddle_tpu.ps.embedding_cache import HbmEmbeddingCache

    dim = 4
    acc = AccessorConfig(embedx_dim=dim, embedx_threshold=0.0,
                         embed_sgd_rule=rule, embedx_sgd_rule=rule,
                         sgd=SGDRuleConfig(initial_range=0.0))
    mirror = MemorySparseTable(TableConfig(shard_num=2, accessor_config=acc))
    backing = MemorySparseTable(TableConfig(shard_num=2, accessor_config=acc))
    cache = HbmEmbeddingCache(backing, CacheConfig(
        capacity=256, embedx_dim=dim, embedx_threshold=0.0,
        embed_rule=rule, embedx_rule=rule))

    keys = np.arange(1, 101, dtype=np.uint64)
    cache.begin_pass(keys)
    for it in range(3):
        bkeys = rng.integers(1, 101, size=64).astype(np.uint64)
        push = np.zeros((64, 4 + dim), np.float32)
        push[:, 1] = 1.0
        push[:, 2] = (rng.random(64) < 0.4).astype(np.float32)
        push[:, 3:] = rng.normal(size=(64, 1 + dim)).astype(np.float32)
        mirror.push_sparse(bkeys, push)

        rows = jnp.asarray(cache.lookup(bkeys), jnp.int32)
        cache.state = cache_push(
            cache.state, rows, jnp.asarray(push[:, 3:]),
            jnp.asarray(push[:, 1]), jnp.asarray(push[:, 2]), cache.config)
    cache.end_pass()

    np.testing.assert_allclose(
        backing.pull_sparse(keys, create=False),
        mirror.pull_sparse(keys, create=False), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("create_applies_grad", [True, False])
@pytest.mark.parametrize("embed_rule,embedx_rule", PAIRS)
def test_dense_push_matches_sparse(rng, embed_rule, embedx_rule,
                                   create_applies_grad):
    """push_mode="dense" (scatter-add + masked full-table update — the
    TPU hot path) == push_mode="sparse" (the merge_grad shape) up to f32
    re-association of duplicate-row sums, including: heavy duplicates,
    the capacity sentinel, zero-show masked padding rows (must stay
    bit-untouched), and untouched rows (must stay bit-untouched)."""
    C, dim, n = 512, 4, 600
    state = _state(rng, C, dim, embed_rule, embedx_rule)
    # heavy duplication (rows drawn from 64) + sentinel padding tail
    rows = rng.integers(0, 64, n).astype(np.int32)
    rows[-40:] = C  # missing-key / padding sentinel
    # row 100 appears ONLY at masked positions: both paths must still
    # apply the rule at zero delta (Adam decays m/v) — batch presence,
    # not show, decides "touched"
    rows[10:20] = 100
    rows = jnp.asarray(rows)
    grads = rng.normal(size=(n, 1 + dim)).astype(np.float32)
    shows = np.ones((n,), np.float32)
    # a masked (weight=0) position ships zero show AND zero grad
    shows[10:20] = 0.0
    grads[10:20] = 0.0
    clicks = (rng.random(n) < 0.4).astype(np.float32) * shows
    grads, shows, clicks = map(jnp.asarray, (grads, shows, clicks))

    kw = dict(capacity=C, embedx_dim=dim, embedx_threshold=3.0,
              embed_rule=embed_rule, embedx_rule=embedx_rule,
              create_applies_grad=create_applies_grad)
    cfg_s = CacheConfig(push_mode="sparse", **kw)
    cfg_d = CacheConfig(push_mode="dense", **kw)
    a = jax.jit(lambda st: cache_push(st, rows, grads, shows, clicks, cfg_s))(state)
    b = jax.jit(lambda st: cache_push(st, rows, grads, shows, clicks, cfg_d))(state)
    for k in a:
        np.testing.assert_allclose(np.asarray(b[k]), np.asarray(a[k]),
                                   rtol=2e-5, atol=1e-6, err_msg=k)
    np.testing.assert_array_equal(np.asarray(b["has_embedx"]),
                                  np.asarray(a["has_embedx"]))
    # rows absent from the batch are bit-identical in the dense path
    touched = np.zeros(C, bool)
    r_np = np.asarray(rows)
    touched[r_np[r_np < C]] = True
    for k in a:
        np.testing.assert_array_equal(
            np.asarray(b[k])[~touched[: C]],
            np.asarray(state[k])[~touched[: C]], err_msg=f"untouched {k}")

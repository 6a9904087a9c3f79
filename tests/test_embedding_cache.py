"""HBM embedding cache: pass lifecycle, in-graph pull/push math parity
with the host-table AdaGrad rule, flush-back correctness (reference:
heter_ps/test_comm.cu pull/push on fake keys + EndPass dump)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ps import (
    AccessorConfig,
    CacheConfig,
    HbmEmbeddingCache,
    MemorySparseTable,
    SGDRuleConfig,
    TableConfig,
    cache_pull,
    cache_push,
)


def make_setup(embedx_threshold=0.5, capacity=64):
    sgd = SGDRuleConfig(learning_rate=0.1, initial_g2sum=3.0)
    acc = AccessorConfig(embedx_dim=4, embedx_threshold=embedx_threshold, sgd=sgd)
    table = MemorySparseTable(TableConfig(shard_num=2, accessor_config=acc))
    cache = HbmEmbeddingCache(
        table, CacheConfig(capacity=capacity, embedx_dim=4, sgd=sgd,
                           embedx_threshold=embedx_threshold)
    )
    return table, cache


def test_pass_lifecycle_pull_push_flush():
    table, cache = make_setup()
    keys = np.asarray([10, 20, 30, 40], np.uint64)
    n = cache.begin_pass(keys)
    assert n == 4

    rows = cache.lookup(keys)
    vals = cache_pull(cache.state, jnp.asarray(rows))
    assert vals.shape == (4, 5)

    # push one gradient step with shows
    grads = jnp.ones((4, 5), jnp.float32) * 0.5
    cache.state = jax.jit(
        lambda st, r, g: cache_push(st, r, g, jnp.ones(4), jnp.ones(4), cache.config)
    )(cache.state, jnp.asarray(rows), grads)

    after = np.asarray(cache_pull(cache.state, jnp.asarray(rows)))
    assert np.abs(after[:, 0]).sum() > 0  # embed moved

    cache.end_pass()
    assert cache.state is None
    # host table saw the flushed values
    host_vals = table.pull_sparse(keys)
    np.testing.assert_allclose(host_vals[:, 0], 1.0, rtol=1e-5)  # shows
    np.testing.assert_allclose(host_vals[:, 2], after[:, 0], rtol=1e-5)  # embed_w


def test_cache_push_matches_host_adagrad():
    """Device AdaGrad must equal the host sparse_sgd_rule math."""
    table, cache = make_setup(embedx_threshold=100.0)  # keep embedx lazy
    keys = np.asarray([7], np.uint64)
    cache.begin_pass(keys)
    rows = jnp.asarray(cache.lookup(keys))

    g = 0.3
    show = 2.0
    w_before = float(np.asarray(cache.state["embed_w"])[int(rows[0]), 0])
    cache.state = cache_push(
        cache.state, rows,
        jnp.asarray([[g, 0, 0, 0, 0]], jnp.float32),
        jnp.asarray([show]), jnp.asarray([0.0]), cache.config,
    )
    dev_w = float(np.asarray(cache.state["embed_w"])[int(rows[0]), 0])

    # host-side reference math (delta, since init weight is random ±1e-4)
    scaled = g / show
    expect = -0.1 * scaled * np.sqrt(3.0 / 3.0)
    np.testing.assert_allclose(dev_w - w_before, expect, rtol=1e-4)
    g2 = float(np.asarray(cache.state["embed_state"])[int(rows[0]), 0])
    np.testing.assert_allclose(g2, scaled * scaled, rtol=1e-5)


def test_duplicate_rows_merge_like_reference():
    """Duplicate keys in a batch merge (sum) before one rule application —
    the cub merge_grad semantics."""
    table, cache = make_setup(embedx_threshold=100.0)
    keys = np.asarray([5], np.uint64)
    cache.begin_pass(keys)
    r = int(cache.lookup(keys)[0])
    w_before = float(np.asarray(cache.state["embed_w"])[r, 0])
    rows = jnp.asarray([r, r, r])
    grads = jnp.asarray([[0.1, 0, 0, 0, 0]] * 3, jnp.float32)
    st = cache_push(cache.state, rows, grads, jnp.ones(3), jnp.zeros(3), cache.config)
    # one merged update: g_sum=0.3, show_sum=3
    scaled = 0.3 / 3.0
    expect = -0.1 * scaled
    np.testing.assert_allclose(
        float(np.asarray(st["embed_w"])[r, 0]) - w_before, expect, rtol=1e-4
    )
    assert float(np.asarray(st["show"])[r]) == 3.0


def test_lazy_embedx_materializes_on_device():
    table, cache = make_setup(embedx_threshold=2.0)
    keys = np.asarray([9], np.uint64)
    cache.begin_pass(keys)
    r = int(cache.lookup(keys)[0])
    rows = jnp.asarray([r])
    # first push: score below threshold (show=1 → score=0.1)
    st = cache_push(cache.state, rows, jnp.ones((1, 5)) * 0.1,
                    jnp.ones(1), jnp.zeros(1), cache.config)
    assert float(np.asarray(st["has_embedx"])[r]) == 0.0
    # heavy clicks push it over (click_coeff=1)
    st2 = cache_push(st, rows, jnp.ones((1, 5)) * 0.1,
                     jnp.asarray([5.0]), jnp.asarray([5.0]), cache.config)
    assert float(np.asarray(st2["has_embedx"])[r]) == 1.0


def test_lookup_outside_pass_raises():
    table, cache = make_setup()
    cache.begin_pass(np.asarray([1, 2], np.uint64))
    with pytest.raises(Exception):
        cache.lookup(np.asarray([999], np.uint64))


def test_roundtrip_preserves_g2sum_across_passes():
    table, cache = make_setup(embedx_threshold=100.0)
    keys = np.asarray([11], np.uint64)
    cache.begin_pass(keys)
    rows = jnp.asarray(cache.lookup(keys))
    st = cache_push(cache.state, rows, jnp.asarray([[0.5, 0, 0, 0, 0]]),
                    jnp.ones(1), jnp.zeros(1), cache.config)
    g2_first = float(np.asarray(st["embed_state"])[int(rows[0]), 0])
    cache.state = st
    cache.end_pass()

    cache.begin_pass(keys)
    r2 = int(cache.lookup(keys)[0])
    g2_reloaded = float(np.asarray(cache.state["embed_state"])[r2, 0])
    np.testing.assert_allclose(g2_reloaded, g2_first, rtol=1e-6)


# ---------------------------------------------------------------------------
# what a row number is: the index's dense number (host lookup), the same
# spread over the shards, or — device_map=True and the slot table fits —
# the key's own slot in the key map. A pass's result must not depend on it.
# ---------------------------------------------------------------------------

_S, _DIM, _N_KEYS, _BATCH = 6, 4, 50, 16     # 300 keys a pass


def _pass_inputs():
    rng = np.random.default_rng(7)
    lo = rng.integers(0, 1 << 20, size=(_N_KEYS, _S)).astype(np.uint64)
    pool = lo + (np.arange(_S, dtype=np.uint64) << np.uint64(32))
    idx = rng.integers(0, _N_KEYS, size=(3, _BATCH))
    dense = rng.normal(size=(3, _BATCH, 3)).astype(np.float32)
    labels = (rng.random((3, _BATCH)) < 0.4).astype(np.int32)
    return pool, idx, dense, labels


def _run_pass(capacity, device_map, mesh=None):
    """Three steps of the key-fed step (row-fed with ``device_map``
    False), then ``end_pass``: (host table rows of the pass's keys, the
    cache's ``pt.pass.begin`` counts, shard of each key's row)."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.core import profiler
    from paddle_tpu.models.ctr import (CtrConfig, DeepFM,
                                       make_ctr_train_step,
                                       make_ctr_train_step_from_keys)
    from paddle_tpu.ps.sharded_cache import (
        check_route_overflow, make_sharded_ctr_train_step_from_keys)

    pool, idx, dense, labels = _pass_inputs()
    pt.seed(0)
    profiler.start_timeline()
    cfg = CacheConfig(capacity=capacity, embedx_dim=_DIM,
                      embedx_threshold=0.0)
    table = MemorySparseTable(TableConfig(
        shard_num=4, accessor_config=AccessorConfig(embedx_dim=_DIM)))
    kw = {} if mesh is None else {"mesh": mesh, "axis": "ps"}
    cache = HbmEmbeddingCache(table, cfg, device_map=device_map, **kw)
    cache.begin_pass(pool.reshape(-1))
    (begin,) = [s for s in profiler.host_spans() if s.name == "pt.pass.begin"]
    model = DeepFM(CtrConfig(num_sparse_slots=_S, num_dense=3,
                             embedx_dim=_DIM, dnn_hidden=(16,)))
    opt = optimizer.Adam(learning_rate=1e-3)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    opt_state = opt.init(params)
    every = np.unique(pool.reshape(-1))
    rows = cache.lookup(every)
    assert len(np.unique(rows)) == len(every)
    if device_map:
        from paddle_tpu.ps.device_hash import split_keys

        # the host's rows ARE the probe's
        probed = cache.device_map.lookup(
            *[jnp.asarray(a) for a in split_keys(every)])
        np.testing.assert_array_equal(np.asarray(probed), rows)
    if mesh is not None:
        step = make_sharded_ctr_train_step_from_keys(
            model, opt, cfg, mesh, slot_ids=np.arange(_S), axis="ps",
            donate=False)
    elif device_map:
        step = make_ctr_train_step_from_keys(
            model, opt, cfg, slot_ids=np.arange(_S), donate=False)
    else:
        step = make_ctr_train_step(model, opt, cfg, donate=False)
    for t in range(3):
        keys = pool[idx[t]]
        if device_map:
            lo32 = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            out = step(params, opt_state, cache.state,
                       cache.device_map.state, jnp.asarray(lo32),
                       jnp.asarray(dense[t]), jnp.asarray(labels[t]))
        else:
            r = cache.lookup(keys.reshape(-1)).reshape(keys.shape)
            out = step(params, opt_state, cache.state, jnp.asarray(r),
                       jnp.asarray(dense[t]), jnp.asarray(labels[t]))
        params, opt_state, cache.state = out[:3]
        if mesh is not None:
            check_route_overflow(out[4])     # raises unless 0
            assert int(out[4]) == 0
    cache.end_pass()
    vals, found = table.export_full(every)
    assert found.all()
    shards = 1 if mesh is None else mesh.shape["ps"]
    return vals, begin.counts, rows // (capacity // shards), np.asarray(out[3])


@pytest.fixture(scope="module")
def dense_rows_pass():
    """The pass under dense row numbers: host lookup, row-fed step."""
    vals, counts, _, loss = _run_pass(1024, device_map=False)
    assert counts["implicit_rows"] == 0
    return vals, loss


@pytest.mark.parametrize("capacity,implicit", [
    (1024, 1),      # 300 keys: 1024 slots fit 1024 rows
    (512, 0)])      # between the key count and the slots: explicit rows
def test_pass_result_does_not_depend_on_row_numbers(capacity, implicit,
                                                    dense_rows_pass):
    """Three key-fed steps then ``end_pass``: the flushed host table is
    bit for bit the one dense row numbers leave, with implicit rows and
    on the explicit form; ``pt.pass.begin`` says which it was."""
    vals, counts, _, loss = _run_pass(capacity, device_map=True)
    assert counts["implicit_rows"] == implicit
    assert counts["capacity"] == capacity and counts["shards"] == 1
    np.testing.assert_array_equal(loss, dense_rows_pass[1])
    np.testing.assert_array_equal(vals, dense_rows_pass[0])


@pytest.mark.parametrize("slack", [1, 4])
def test_implicit_rows_on_a_mesh_balance_and_match_one_chip(slack,
                                                            dense_rows_pass):
    """Four shards, capacity = the slot table and four times it: every
    shard holds n/K of the pass's keys within 5 sigma (the bucket's low
    bits pick the shard), the routed step drops nothing, and the flushed
    table matches the one-chip pass."""
    from paddle_tpu.core import mesh as mesh_mod

    mesh = mesh_mod.make_mesh({"ps": 4}, devices=jax.devices()[:4])
    vals, counts, shard_of, _ = _run_pass(1024 * slack, device_map=True,
                                          mesh=mesh)
    assert counts["implicit_rows"] == 1 and counts["shards"] == 4
    n = len(shard_of)
    held = np.bincount(shard_of, minlength=4)
    assert np.abs(held - n / 4).max() < 5 * np.sqrt(n * 0.25 * 0.75), held
    # the routed step pre-merges a device's duplicates: ~1 ulp off the
    # one-chip sums (sharded_cache's docstring), never a dropped update
    np.testing.assert_array_equal(vals[:, :5], dense_rows_pass[0][:, :5])
    np.testing.assert_allclose(vals, dense_rows_pass[0], rtol=2e-5, atol=1e-7)

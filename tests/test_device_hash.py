"""Device-resident cuckoo key→row map (ps/device_hash.py + csrc/cuckoo.cc)
— the GPU HashTable::get analogue (heter_ps/hashtable_inl.h) probed
in-graph; and the key-fed CTR step that fuses the probe into the program.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer
from paddle_tpu.models.ctr import (CtrConfig, DeepFM, make_ctr_train_step,
                                   make_ctr_train_step_from_keys)
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.device_hash import (DeviceKeyMap, DynamicDeviceKeyMap,
                                       dynamic_map_lookup, split_keys)
from paddle_tpu.ps.embedding_cache import CacheConfig, HbmEmbeddingCache
from paddle_tpu.ps.table import MemorySparseTable, TableConfig


def test_device_map_exact_and_missing(rng):
    keys = np.unique(rng.integers(1, 1 << 62, size=5000, dtype=np.uint64))
    rows = rng.permutation(len(keys)).astype(np.int32)
    m = DeviceKeyMap(keys, rows)

    batch = keys[rng.integers(0, len(keys), size=2000)]
    got = np.asarray(m.lookup(*[jnp.asarray(a) for a in split_keys(batch)]))
    want = rows[np.searchsorted(keys, batch)]
    np.testing.assert_array_equal(got, want)

    miss = rng.integers(1 << 62, 1 << 63, size=500, dtype=np.uint64)
    got = np.asarray(m.lookup(*[jnp.asarray(a) for a in split_keys(miss)]))
    assert (got == -1).all()


def test_device_map_low_bit_keys(rng):
    # hi half all zeros (plain small ids) must still disambiguate
    keys = np.unique(rng.integers(1, 1 << 30, size=4096, dtype=np.uint64))
    rows = np.arange(len(keys), dtype=np.int32)
    m = DeviceKeyMap(keys, rows)
    got = np.asarray(m.lookup(*[jnp.asarray(a) for a in split_keys(keys)]))
    np.testing.assert_array_equal(got, rows)


def test_key_fed_step_matches_row_fed(rng):
    """The in-graph lookup step produces the identical trajectory to the
    host-lookup step (same rows → same math)."""
    S, dim = 6, 4
    ccfg = CtrConfig(num_sparse_slots=S, num_dense=3, embedx_dim=dim,
                     dnn_hidden=(16,))
    cache_cfg = CacheConfig(capacity=1 << 11, embedx_dim=dim,
                            embedx_threshold=0.0)
    n_keys, batch = 200, 16
    # slot-tagged keys: hi = column slot id
    lo = rng.integers(0, 1 << 20, size=(n_keys, S)).astype(np.uint64)
    pool = lo + (np.arange(S, dtype=np.uint64) << np.uint64(32))

    def build():
        pt.seed(0)
        table = MemorySparseTable(TableConfig(
            shard_num=4, accessor_config=AccessorConfig(embedx_dim=dim)))
        cache = HbmEmbeddingCache(table, cache_cfg, device_map=True)
        cache.begin_pass(pool.reshape(-1))
        model = DeepFM(ccfg)
        opt = optimizer.Adam(learning_rate=1e-3)
        params = {"params": dict(model.named_parameters()), "buffers": {}}
        return table, cache, model, opt, params, opt.init(params)

    idx = rng.integers(0, n_keys, size=(3, batch))
    dense = rng.normal(size=(3, batch, 3)).astype(np.float32)
    labels = (rng.random((3, batch)) < 0.4).astype(np.int32)

    # row-fed reference
    table1, cache1, model1, opt1, params1, opt_state1 = build()
    step1 = make_ctr_train_step(model1, opt1, cache_cfg, donate=False)
    for t in range(3):
        keys = pool[idx[t]]
        rows = jnp.asarray(cache1.lookup(keys.reshape(-1)).reshape(keys.shape))
        params1, opt_state1, cache1.state, loss1 = step1(
            params1, opt_state1, cache1.state, rows,
            jnp.asarray(dense[t]), jnp.asarray(labels[t]))

    # key-fed
    table2, cache2, model2, opt2, params2, opt_state2 = build()
    step2 = make_ctr_train_step_from_keys(model2, opt2, cache_cfg,
                                          slot_ids=np.arange(S), donate=False)
    for t in range(3):
        lo32 = (pool[idx[t]] & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        params2, opt_state2, cache2.state, loss2 = step2(
            params2, opt_state2, cache2.state, cache2.device_map.state,
            jnp.asarray(lo32), jnp.asarray(dense[t]), jnp.asarray(labels[t]))

    np.testing.assert_array_equal(np.asarray(loss1), np.asarray(loss2))
    for k in cache1.state:
        np.testing.assert_array_equal(
            np.asarray(cache1.state[k]), np.asarray(cache2.state[k]),
            err_msg=f"cache[{k}]")


def test_wide_key_step_matches_slot_tagged(rng):
    """slot_ids=None variant (explicit hi halves) gives the identical
    trajectory when fed the same keys."""
    S, dim = 4, 4
    ccfg = CtrConfig(num_sparse_slots=S, num_dense=2, embedx_dim=dim,
                     dnn_hidden=(8,))
    cache_cfg = CacheConfig(capacity=1 << 10, embedx_dim=dim,
                            embedx_threshold=0.0)
    lo = rng.integers(0, 1 << 20, size=(100, S)).astype(np.uint64)
    pool = lo + (np.arange(S, dtype=np.uint64) << np.uint64(32))

    def build():
        pt.seed(0)
        table = MemorySparseTable(TableConfig(
            shard_num=4, accessor_config=AccessorConfig(embedx_dim=dim)))
        cache = HbmEmbeddingCache(table, cache_cfg, device_map=True)
        cache.begin_pass(pool.reshape(-1))
        model = DeepFM(ccfg)
        opt = optimizer.Adam(learning_rate=1e-3)
        params = {"params": dict(model.named_parameters()), "buffers": {}}
        return cache, model, opt, params, opt.init(params)

    idx = rng.integers(0, 100, size=16)
    keys = pool[idx]
    dense = rng.normal(size=(16, 2)).astype(np.float32)
    labels = (rng.random(16) < 0.4).astype(np.int32)
    lo32 = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi32 = (keys >> np.uint64(32)).astype(np.uint32)

    c1, m1, o1, p1, s1 = build()
    step1 = make_ctr_train_step_from_keys(m1, o1, cache_cfg,
                                          slot_ids=np.arange(S), donate=False)
    _, _, st1, loss1 = step1(p1, s1, c1.state, c1.device_map.state,
                             jnp.asarray(lo32), jnp.asarray(dense),
                             jnp.asarray(labels))

    c2, m2, o2, p2, s2 = build()
    step2 = make_ctr_train_step_from_keys(m2, o2, cache_cfg, slot_ids=None,
                                          donate=False)
    _, _, st2, loss2 = step2(p2, s2, c2.state, c2.device_map.state,
                             jnp.asarray(hi32), jnp.asarray(lo32),
                             jnp.asarray(dense), jnp.asarray(labels))
    np.testing.assert_array_equal(np.asarray(loss1), np.asarray(loss2))
    for k in st1:
        np.testing.assert_array_equal(np.asarray(st1[k]), np.asarray(st2[k]))


# ---------------------------------------------------------------------------
# the hot tier's dynamic map, banked: the in-graph probe against the host
# mirror (the tier's placement contract: a key's row lives inside its
# bank's contiguous row block; banks=8 is one bank a shard of the 8-shard
# tier)
# ---------------------------------------------------------------------------


def _banked_map(C, banks, keys):
    m = DynamicDeviceKeyMap(C, banks=banks)
    Cb = C // banks
    rows = np.zeros(len(keys), np.int32)
    nxt = [0] * banks
    for i, b in enumerate(m.bank_of(keys)):
        rows[i] = b * Cb + nxt[b]
        nxt[b] += 1
    assert max(nxt) <= Cb
    m.insert(keys, rows)
    return m, rows


def _dyn_lookup(m, keys):
    hi, lo = split_keys(keys)
    return np.asarray(dynamic_map_lookup(
        m.device_state(), jnp.asarray(hi), jnp.asarray(lo), m.probe_buckets,
        m.banks))


@pytest.mark.parametrize("banks", [1, 4, 8])
def test_probe_gather_matches_jnp_reference(banks):
    """The banked ``dynamic_map_lookup`` resolves every resident key to
    the row the host gave it and every absent key to -1, at an unaligned
    n (157 probes)."""
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(1, 2**63, 300).astype(np.uint64))[:120]
    m, rows = _banked_map(256, banks, keys)
    absent = rng.integers(1, 2**63, 37).astype(np.uint64)
    got = _dyn_lookup(m, np.concatenate([keys, absent]))
    np.testing.assert_array_equal(got[:len(keys)], rows)
    assert (got[len(keys):] == -1).all()
    # a bank's rows never leave its block
    np.testing.assert_array_equal(rows // (256 // banks), m.bank_of(keys))


@pytest.mark.parametrize("banks", [1, 4, 8])
def test_probe_gather_after_mutation_and_rebuild(banks):
    """Evict/insert churn (incremental device patches) and a grow
    rebuild (full re-upload, new probe seed): the in-graph probe reads
    the state the host mirror describes through both."""
    rng = np.random.default_rng(1)
    keys = np.unique(rng.integers(1, 2**63, 300).astype(np.uint64))[:96]
    m, rows = _banked_map(256, banks, keys)

    def check():
        np.testing.assert_array_equal(_dyn_lookup(m, keys),
                                      m.lookup_host(keys))

    check()
    np.testing.assert_array_equal(m.lookup_host(keys), rows)
    m.remove(keys[::3])          # tombstones → incremental patches
    check()
    assert (m.lookup_host(keys[::3]) == -1).all()
    m._rebuild(grow=False)       # reseed
    check()
    m._rebuild(grow=True)        # grow → full re-upload
    check()
    np.testing.assert_array_equal(m.lookup_host(keys[1::3]), rows[1::3])


def test_bank_membership_stable_across_rebuilds():
    """bank_of is a FIXED hash: reseed and grow rebuilds relocate
    buckets but never move a key between banks (the tier's row blocks
    depend on it)."""
    rng = np.random.default_rng(4)
    m = DynamicDeviceKeyMap(256, banks=8)
    keys = np.unique(rng.integers(1, 2**63, 300).astype(np.uint64))[:128]
    before = m.bank_of(keys)
    m.insert(keys, np.arange(len(keys), dtype=np.int32))
    m._rebuild(grow=False)   # reseed
    m._rebuild(grow=True)    # grow
    np.testing.assert_array_equal(m.bank_of(keys), before)
    np.testing.assert_array_equal(m.lookup_host(keys),
                                  np.arange(len(keys), dtype=np.int32))
    # banked probe never resolves a key through another bank's region:
    # the in-graph lookup agrees with the host mirror on every key
    np.testing.assert_array_equal(_dyn_lookup(m, keys), m.lookup_host(keys))

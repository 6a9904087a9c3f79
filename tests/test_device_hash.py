"""Device-resident cuckoo key→row map (ps/device_hash.py + csrc/cuckoo.cc)
— the GPU HashTable::get analogue (heter_ps/hashtable_inl.h) probed
in-graph; and the key-fed CTR step that fuses the probe into the program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer
from paddle_tpu.models.ctr import (CtrConfig, DeepFM, make_ctr_train_step,
                                   make_ctr_train_step_from_keys)
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.device_hash import (_SEED2_XOR, DeviceKeyMap,
                                       DynamicDeviceKeyMap, _filler_keys,
                                       _mix32_np, device_hash_lookup,
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


# ---------------------------------------------------------------------------
# the packed map: a bucket's four keys in ONE row of ``key`` (hi×4 | lo×4),
# its rows in ``row`` (explicit rows: the caller chose them) — four bucket
# gathers a probe
# ---------------------------------------------------------------------------


def _probe(m, keys):
    return np.asarray(m.lookup(*[jnp.asarray(a) for a in split_keys(keys)]))


def _host_probe(state, keys):
    """The probe spelled out over the host copy of the map, one slot at a
    time (both candidate buckets, hi and lo compared apart, ``row >= 0``
    guarding empty slots)."""
    key, row = np.asarray(state["key"]), np.asarray(state["row"])
    seed = int(state["seed"])
    hi, lo = split_keys(keys)
    found = np.full(len(keys), -1, np.int32)
    for s in (seed, seed ^ int(_SEED2_XOR)):
        b = _mix32_np(hi, lo, s) & np.uint32(len(row) - 1)
        for slot in range(4):
            hit = ((key[b, slot] == hi) & (key[b, 4 + slot] == lo)
                   & (row[b, slot] >= 0))
            found = np.where(hit, row[b, slot], found)
    return found


def _packed_case(name, rng):
    """(keys in the map, keys to probe) of one parity case."""
    base = np.unique(rng.integers(1, 1 << 63, size=3000, dtype=np.uint64))
    if name == "random":
        return base, np.concatenate([
            base[rng.integers(0, len(base), 2000)],
            rng.integers(1, 1 << 63, size=500, dtype=np.uint64)])
    if name == "differ_only_in_hi":
        # one lo half under many hi halves, half of them in the map
        lo = np.uint64(0x9ABCDEF1)
        pairs = (np.arange(1, 801, dtype=np.uint64) << np.uint64(32)) | lo
        return pairs[::2], pairs
    if name == "differ_only_in_lo":
        hi = np.uint64(0x1234567) << np.uint64(32)
        pairs = hi | np.arange(1, 801, dtype=np.uint64)
        return pairs[::2], pairs
    if name == "halves_swapped":
        # (hi, lo) in the map, (lo, hi) probed: the halves must not be
        # compared against each other's words
        hi, lo = split_keys(base)
        swapped = (lo.astype(np.uint64) << np.uint64(32)) | hi
        return base, np.concatenate([base, swapped])
    if name == "absent":
        return base, rng.integers(1, 1 << 63, size=2000, dtype=np.uint64)
    if name == "zero_absent":
        # empty slots hold zero key words: key 0 must still read -1
        return base, np.zeros(64, np.uint64)
    if name == "zero_present":
        return np.concatenate([np.zeros(1, np.uint64), base]), \
            np.concatenate([np.zeros(3, np.uint64), base[:100]])
    raise AssertionError(name)


@pytest.mark.parametrize("case", [
    "random", "differ_only_in_hi", "differ_only_in_lo", "halves_swapped",
    "absent", "zero_absent", "zero_present"])
def test_packed_probe_matches_host_dict(case, rng):
    """Every key the map holds reads the row it was given, every other
    key -1 — against a host dict, and bit for bit against the probe
    spelled out slot by slot over the host copy of the arrays."""
    keys, probe = _packed_case(case, rng)
    rows = rng.permutation(len(keys)).astype(np.int32)
    m = DeviceKeyMap(keys, rows)
    assert (np.asarray(m.state["row"]) == -1).any()      # empty slots exist
    want = dict(zip(keys.tolist(), rows.tolist()))
    got = _probe(m, probe)
    np.testing.assert_array_equal(
        got, np.asarray([want.get(k, -1) for k in probe.tolist()], np.int32))
    np.testing.assert_array_equal(got, _host_probe(m.state, probe))


def test_packed_map_layout():
    """``state`` is {key u32[nb, 8], row i32[nb, 4], seed}: a bucket's
    row of ``key`` is its four hi halves then its four lo halves, slot
    for slot with ``row``; empty slots are row -1 over zero key words."""
    rng = np.random.default_rng(3)
    keys = np.unique(rng.integers(0, 1 << 63, size=700, dtype=np.uint64))
    rows = np.arange(len(keys), dtype=np.int32)
    m = DeviceKeyMap(keys, rows)
    assert sorted(m.state) == ["key", "row", "seed"]
    key, row = np.asarray(m.state["key"]), np.asarray(m.state["row"])
    nb = m.nbuckets
    assert key.shape == (nb, 8) and key.dtype == np.uint32
    assert row.shape == (nb, 4) and row.dtype == np.int32
    live = row >= 0
    stored = (key[:, :4].astype(np.uint64) << np.uint64(32)) | key[:, 4:]
    np.testing.assert_array_equal(np.sort(stored[live]), keys)
    np.testing.assert_array_equal(stored[live][np.argsort(row[live])], keys)
    assert (row[~live] == -1).all() and (stored[~live] == 0).all()


@pytest.mark.parametrize("form,shapes", [
    ("implicit", [(96, 8), (96, 8)]),
    ("explicit", [(96, 4), (96, 4), (96, 8), (96, 8)])])
def test_probe_gathers(form, shapes):
    """One row of ``key`` a hash, and with explicit rows one of ``row``
    too: the jaxpr and the lowered module of ``device_hash_lookup`` hold
    exactly two gathers, both 8 wide, where a row is its slot, and four
    (two 8 wide, two 4 wide) where the map stores rows (a count: CPU)."""
    nb, n = 256, 96
    table = {"key": jnp.zeros((nb, 8), jnp.uint32), "seed": jnp.uint32(7)}
    if form == "explicit":
        table["row"] = jnp.full((nb, 4), -1, jnp.int32)
    else:
        table.update(shard_shift=jnp.int32(2), shard_rows=jnp.int32(nb))
    k = jnp.zeros((n,), jnp.uint32)
    jaxpr = jax.make_jaxpr(device_hash_lookup)(table, k, k)

    def gathers(jp):
        out = []
        for eqn in jp.eqns:
            if eqn.primitive.name == "gather":
                out.append(eqn.outvars[0].aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                out += gathers(sub)
        return out

    assert sorted(gathers(jaxpr.jaxpr)) == shapes
    lowered = jax.jit(device_hash_lookup).lower(table, k, k).as_text()
    assert lowered.count("stablehlo.gather") == len(shapes), lowered


# ---------------------------------------------------------------------------
# implicit rows: a key's row is the slot the build put it in, the map has
# no ``row`` array, empty slots hold a filler key that cannot match
# ---------------------------------------------------------------------------


def _implicit_keys(name, rng):
    if name == "load_half":
        # 512 keys in 256 buckets x 4 slots: load exactly 0.5
        return np.unique(rng.integers(
            1 << 20, 1 << 63, size=700, dtype=np.uint64))[:512]
    if name == "nearly_empty":
        return np.asarray([11, 1 << 40, (7 << 32) | 3], np.uint64)
    if name == "equal_low_halves":
        return (np.arange(1, 401, dtype=np.uint64) << np.uint64(32)) \
            | np.uint64(0x9ABCDEF1)
    raise AssertionError(name)


def _slot_words(state, rows):
    """The key whose words sit in the slot that IS cache row ``rows``."""
    key = np.asarray(state["key"])
    K, block = 1 << int(state["shard_shift"]), int(state["shard_rows"])
    shard, within = rows // block, rows % block
    b, s = (within // 4) * K + shard, within % 4
    return (key[b, s].astype(np.uint64) << np.uint64(32)) | key[b, 4 + s]


@pytest.mark.parametrize("fillers_in_pass", [False, True])
@pytest.mark.parametrize("case", ["load_half", "nearly_empty",
                                  "equal_low_halves"])
def test_implicit_probe_matches_host_dict(case, fillers_in_pass, rng):
    """Every key of the pass reads the position of its own words in
    ``key``, the row the build reported for it; every other key reads
    -1 — key 0 and both filler keys among them, which read their slot
    once the pass holds them: no key value is reserved."""
    keys = _implicit_keys(case, rng)
    nb = DeviceKeyMap.buckets_for(len(keys))
    # the fillers depend on the seed alone: learn them from a first build
    built, _, _ = DeviceKeyMap.build_host_implicit(keys, nb * 4)
    other, _ = _filler_keys(nb, int(built["seed"]))
    assert other != 0
    special = np.asarray([0, other], np.uint64)
    if fillers_in_pass:
        keys = np.concatenate([special, keys[:len(keys) - 2]])
        built, placed_keys, rows = DeviceKeyMap.build_host_implicit(keys, nb * 4)
        assert _filler_keys(nb, int(built["seed"]))[0] == other
    else:
        built, placed_keys, rows = DeviceKeyMap.build_host_implicit(keys, nb * 4)
    m = DeviceKeyMap(host_built=built)
    assert sorted(m.state) == ["key", "seed", "shard_rows", "shard_shift"]
    assert len(rows) == len(keys) and (np.diff(rows) > 0).all()
    np.testing.assert_array_equal(np.sort(placed_keys), np.sort(keys))
    assert 0 <= rows.min() and rows.max() < nb * 4
    want = dict(zip(placed_keys.tolist(), rows.tolist()))

    got = _probe(m, keys)
    np.testing.assert_array_equal(
        got, np.asarray([want[k] for k in keys.tolist()], np.int32))
    np.testing.assert_array_equal(_slot_words(m.state, got), keys)

    absent = np.concatenate([
        rng.integers(1 << 20, 1 << 63, size=500, dtype=np.uint64),
        keys ^ np.uint64(1 << 33), keys ^ np.uint64(1), special])
    absent = absent[~np.isin(absent, keys)]
    assert (_probe(m, absent) == -1).all()
    if fillers_in_pass:
        assert (_probe(m, special) >= 0).all()
    else:
        assert np.isin(special, absent).all()
    # every slot that is no key's row holds a filler
    empty = np.setdiff1d(np.arange(nb * 4), rows)
    assert np.isin(_slot_words(m.state, empty), special).all()


@pytest.mark.parametrize("shards,slack", [(4, 1), (4, 4), (8, 2)])
def test_implicit_rows_over_shards(shards, slack, rng):
    """Over K shards a bucket's LOW bits pick the shard, whatever the
    ratio of capacity to slots: every shard holds n/K keys within 5
    sigma, inside the first slots of its block, and the probe's row is
    the key's own slot."""
    keys = np.unique(rng.integers(1, 1 << 63, size=5000, dtype=np.uint64))
    n, nb = len(keys), DeviceKeyMap.buckets_for(len(keys))
    capacity = nb * 4 * slack
    built, placed_keys, rows = DeviceKeyMap.build_host_implicit(
        keys, capacity, shards)
    m = DeviceKeyMap(host_built=built)
    block = capacity // shards
    assert int(m.state["shard_rows"]) == block
    assert (np.diff(rows) > 0).all()
    assert (rows % block < nb * 4 // shards).all()
    counts = np.bincount(rows // block, minlength=shards)
    sigma = np.sqrt(n * (1 / shards) * (1 - 1 / shards))
    assert np.abs(counts - n / shards).max() < 5 * sigma, counts
    got = _probe(m, placed_keys)
    np.testing.assert_array_equal(got, rows)
    np.testing.assert_array_equal(_slot_words(m.state, got), placed_keys)
    # shards that cannot deal the buckets out evenly keep explicit rows
    assert not DeviceKeyMap.rows_can_be_slots(n, 3 * capacity, 3)
    assert not DeviceKeyMap.rows_can_be_slots(n, nb * 4 - shards, shards)


@pytest.mark.parametrize("capacity,implicit", [
    (1024, True),     # 300 keys: 256 buckets, 1024 slots fit
    (4096, True),
    (512, False),     # more than half full: the slot table does not fit
    (128, False)])    # under the map's 256-slot minimum (100 keys)
def test_cache_chooses_the_form_by_what_fits(capacity, implicit, rng):
    """``HbmEmbeddingCache(device_map=True)``: no ``row`` in the map's
    state when nb*4 <= capacity, today's explicit rows (dense numbers)
    when not; either way ``lookup`` and the probe agree and every row is
    a row of the cache."""
    keys = np.unique(rng.integers(0, 1 << 63, size=400, dtype=np.uint64))
    keys = keys[:300 if capacity >= 512 else 100]
    table = MemorySparseTable(TableConfig(
        shard_num=2, accessor_config=AccessorConfig(embedx_dim=4)))
    cache = HbmEmbeddingCache(
        table, CacheConfig(capacity=capacity, embedx_dim=4), device_map=True)
    cache.begin_pass(keys)
    state = cache.device_map.state
    assert ("row" not in state) == implicit
    rows = cache.lookup(keys)
    np.testing.assert_array_equal(_probe(cache.device_map, keys), rows)
    assert len(np.unique(rows)) == len(keys)
    assert rows.min() >= 0 and rows.max() < capacity
    if not implicit:
        np.testing.assert_array_equal(np.sort(rows), np.arange(len(keys)))
        np.testing.assert_array_equal(_probe(cache.device_map, keys),
                                      _host_probe(state, keys))
    cache.discard_pass()


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

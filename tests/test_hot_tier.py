"""Persistent HBM hot-embedding tier (ps/hot_tier.py): dynamic map
mechanics, hot-tier ≡ RPC-only bit-parity (dense params + pulled rows,
fp32 path), eviction-churn parity, mid-stream checkpoint/restore parity,
the 0-RPC warm-step contract, and the sharded (mesh) step.

The parity oracle story: the tier's device rule math
(ops/sparse_optimizer) is pinned bit-identical to the host engines, so a
tier-enabled run reproduces the RPC-only trainer's final state EXACTLY
on the fp32 path — except ``delta_score`` (save-layout col 2), which
folds per FLUSH instead of per push (the established end_pass
association; documented non-goal in the hot_tier module docstring)."""

import os
import tempfile

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer
from paddle_tpu.core import mesh as mesh_mod
from paddle_tpu.data.dataset import InMemoryDataset, SlotDesc
from paddle_tpu.models.ctr import CtrConfig, DeepFM
from paddle_tpu.ops.sparse_optimizer import rule_state_dim
from paddle_tpu.ps import rpc
from paddle_tpu.ps.communicator import (HalfAsyncCommunicator,
                                         SyncCommunicator)
from paddle_tpu.ps.device_hash import (DynamicDeviceKeyMap,
                                       dynamic_map_lookup, split_keys)
from paddle_tpu.ps.hot_tier import HotEmbeddingTier, HotTierConfig
from paddle_tpu.ps.ps_trainer import CtrStreamTrainer
from paddle_tpu.ps.table import MemorySparseTable, TableConfig

# save-layout column of delta_score — the one per-flush-vs-per-push
# association difference the parity tests carve out
_DELTA_COL = 2


# ---------------------------------------------------------------------------
# DynamicDeviceKeyMap
# ---------------------------------------------------------------------------


def _dev_lookup(m: DynamicDeviceKeyMap, keys: np.ndarray) -> np.ndarray:
    hi, lo = split_keys(keys)
    import jax.numpy as jnp

    return np.asarray(dynamic_map_lookup(m.device_state(), jnp.asarray(hi),
                                         jnp.asarray(lo), m.probe_buckets))


def test_dynamic_map_insert_lookup_remove():
    m = DynamicDeviceKeyMap(64)
    keys = np.arange(1, 33, dtype=np.uint64) * np.uint64(0x9E3779B97F4A7C15)
    rows = np.arange(32, dtype=np.int32)
    m.insert(keys, rows)
    np.testing.assert_array_equal(m.lookup_host(keys), rows)
    # absent keys miss
    assert (m.lookup_host(np.asarray([7, 8, 9], np.uint64)) == -1).all()
    # remove half, the rest still resolve
    m.remove(keys[::2])
    got = m.lookup_host(keys)
    assert (got[::2] == -1).all()
    np.testing.assert_array_equal(got[1::2], rows[1::2])
    assert m.used == 16
    # re-inserting a removed key at a new row works (tombstone reuse)
    m.insert(keys[:1], np.asarray([99], np.int32))
    assert m.lookup_host(keys[:1])[0] == 99


def test_dynamic_map_device_lookup_matches_host():
    m = DynamicDeviceKeyMap(128)
    rng = np.random.default_rng(0)
    keys = rng.integers(1, 2**63, 100).astype(np.uint64)
    keys = np.unique(keys)
    m.insert(keys, np.arange(len(keys), dtype=np.int32))
    probe = np.concatenate([keys, rng.integers(1, 2**63, 50).astype(np.uint64)])
    np.testing.assert_array_equal(_dev_lookup(m, probe), m.lookup_host(probe))
    # mutate (patch path: device arrays update incrementally) and re-check
    m.remove(keys[:10])
    m.insert(rng.integers(1, 2**63, 5).astype(np.uint64)
             | np.uint64(1 << 63),
             np.arange(200, 205, dtype=np.int32))
    np.testing.assert_array_equal(_dev_lookup(m, probe), m.lookup_host(probe))


def test_dynamic_map_rebuild_preserves_entries():
    m = DynamicDeviceKeyMap(64, bucket_slots=1, probe_buckets=1)
    rng = np.random.default_rng(1)
    keys = np.unique(rng.integers(1, 2**63, 60).astype(np.uint64))[:48]
    rows = np.arange(len(keys), dtype=np.int32)
    m.insert(keys, rows)  # 1-slot windows → collisions force rebuilds
    np.testing.assert_array_equal(m.lookup_host(keys), rows)
    # explicit grow-rebuild: layout changes, entries don't
    nb0 = m.nbuckets
    m._rebuild(grow=True)
    assert m.nbuckets == 2 * nb0 and m.rebuilds > 0
    np.testing.assert_array_equal(m.lookup_host(keys), rows)
    np.testing.assert_array_equal(_dev_lookup(m, keys), rows)


def test_dynamic_map_over_capacity_rejected():
    m = DynamicDeviceKeyMap(4)
    with pytest.raises(Exception):
        m.insert(np.arange(1, 7, dtype=np.uint64),
                 np.arange(6, dtype=np.int32))


# ---------------------------------------------------------------------------
# trainer parity harness
# ---------------------------------------------------------------------------

S, D = 3, 2


def make_data(n=256, seed=0, nid=48):
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        ids = rng.integers(0, nid, S)
        dense = rng.normal(size=D)
        label = int((ids % 5 == 0).sum() + dense[0] > 1.0)
        lines.append(" ".join([f"1 {v}" for v in ids]
                              + [f"1 {v:.4f}" for v in dense]
                              + [f"1 {label}"]))
    slots = ([SlotDesc(f"s{i}", is_float=False, max_len=1) for i in range(S)]
             + [SlotDesc(f"d{i}", is_float=True, max_len=1) for i in range(D)]
             + [SlotDesc("label", is_float=True, max_len=1)])
    ds = InMemoryDataset(slots, seed=0)
    ds.load_from_lines(lines)
    return ds


def make_trainer(table, hot=None, communicator=None, table_id=0):
    pt.seed(0)
    return CtrStreamTrainer(
        DeepFM(CtrConfig(num_sparse_slots=S, num_dense=D, embedx_dim=8,
                         dnn_hidden=(8,))),
        optimizer.Adam(1e-2), table, embedx_dim=8,
        sparse_slots=[f"s{i}" for i in range(S)],
        dense_slots=[f"d{i}" for i in range(D)], label_slot="label",
        communicator=communicator, table_id=table_id, hot_tier=hot)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise_equal(ta, tb):
    for a, b in zip(ta, tb):
        np.testing.assert_array_equal(a, b)


def _sorted_items(table):
    k, v = table.snapshot_items()
    i = np.argsort(k)
    return k[i], v[i]


def _assert_rows_equal_mod_delta(ta, tb):
    ka, va = _sorted_items(ta)
    kb, vb = _sorted_items(tb)
    np.testing.assert_array_equal(ka, kb)
    for c in range(va.shape[1]):
        if c == _DELTA_COL:
            continue
        np.testing.assert_array_equal(va[:, c], vb[:, c],
                                      err_msg=f"save col {c}")


def test_hot_tier_parity_bit_identical():
    """Tier-enabled training ≡ RPC-only oracle: dense params bitwise,
    every pulled-row column bitwise except the per-flush delta_score."""
    ds = make_data()
    ta = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    a = make_trainer(ta)
    ra = a.train_from_dataset(ds, batch_size=64)
    tb = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    b = make_trainer(tb, hot=HotTierConfig(capacity=256))
    rb = b.train_from_dataset(ds, batch_size=64)
    b.hot_tier.flush()
    assert ra["loss"] == rb["loss"]
    _assert_bitwise_equal(_leaves(a.params), _leaves(b.params))
    _assert_bitwise_equal(_leaves(a.opt_state), _leaves(b.opt_state))
    _assert_rows_equal_mod_delta(ta, tb)
    st = rb["hot_tier"]
    assert st["misses"] > 0 and st["hits"] > 0 and st["evictions"] == 0
    assert 0 < st["occupancy"] <= st["capacity"]


def test_hot_tier_eviction_churn_parity():
    """Tiny capacity (barely above one batch's working set) forces
    heavy eviction/readmission churn — parity must survive the
    writeback→re-fetch round-trips bit-for-bit."""
    ds = make_data(nid=400)
    ta = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    a = make_trainer(ta)
    a.train_from_dataset(ds, batch_size=64)
    tb = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    b = make_trainer(tb, hot=HotTierConfig(capacity=224))
    rb = b.train_from_dataset(ds, batch_size=64)
    st = rb["hot_tier"]
    assert st["evictions"] > 0 and st["writebacks"] > 0
    b.hot_tier.flush()
    _assert_bitwise_equal(_leaves(a.params), _leaves(b.params))
    _assert_rows_equal_mod_delta(ta, tb)


def test_hot_tier_capacity_below_batch_working_set_raises():
    ds = make_data(nid=400)
    tb = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    b = make_trainer(tb, hot=HotTierConfig(capacity=64))  # < 64*3 keys
    with pytest.raises(Exception, match="capacity"):
        b.train_from_dataset(ds, batch_size=64)


def test_hot_tier_checkpoint_restore_parity():
    """Mid-stream checkpoint → fresh process-equivalent restore →
    resume: final table digests AND dense params/opt bitwise equal to an
    uninterrupted tier-enabled oracle checkpointing at the same cadence
    (same flush points ⇒ same delta_score association ⇒ full digest
    equality, not just mod-delta)."""
    from paddle_tpu.io.job_checkpoint import JobCheckpointManager

    tmp = tempfile.mkdtemp()
    ds = make_data(n=640, nid=120)
    ta = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    a = make_trainer(ta, hot=HotTierConfig(capacity=256))
    mga = JobCheckpointManager(os.path.join(tmp, "a"), max_keep=8)
    mga.register_sparse("ctr", ta)
    a.train_from_dataset(ds, batch_size=128, checkpoint=mga,
                         checkpoint_every=2)
    mga.stop()
    a.hot_tier.flush()

    tb = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    b = make_trainer(tb, hot=HotTierConfig(capacity=256))
    mgr = JobCheckpointManager(os.path.join(tmp, "b"), max_keep=8)
    mgr.register_sparse("ctr", tb)
    b.train_from_dataset(ds, batch_size=128, checkpoint=mgr,
                         checkpoint_every=2)
    mgr.wait()
    restored = mgr.load_latest()
    assert restored.cursor["batch"] > 0

    tc = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    c = make_trainer(tc, hot=HotTierConfig(capacity=256))
    restored.restore_sparse("ctr", tc)
    c.restore_train_state(restored.dense)
    # restore drops the resident set (stale vs the rebuilt cold table)
    assert c.hot_tier.stats()["occupancy"] == 0
    out = c.train_from_dataset(ds, batch_size=128,
                               start_batch=restored.cursor)
    assert out["steps"] > 0
    c.hot_tier.flush()
    mgr.stop()
    assert tc.digest() == ta.digest()
    _assert_bitwise_equal(_leaves(a.params), _leaves(c.params))
    _assert_bitwise_equal(_leaves(a.opt_state), _leaves(c.opt_state))


def test_hot_tier_warm_steady_state_zero_rpcs():
    """THE acceptance criterion: once the working set is resident, a
    steady-state epoch over a real RPC PS performs ZERO client ops —
    counted at RpcPsClient, the hot-tier CI gate's counter."""
    servers = [rpc.NativePsServer(n_trainers=1) for _ in range(2)]
    client = rpc.RpcPsClient([f"127.0.0.1:{s.port}" for s in servers])
    try:
        client.create_sparse_table(
            0, TableConfig(table_id=0, shard_num=4, accessor="ctr"))
        comm = HalfAsyncCommunicator(client)
        comm.start()
        tr = make_trainer(None, hot=HotTierConfig(capacity=512),
                          communicator=comm)
        ds = make_data(n=512, nid=60)
        tr.train_from_dataset(ds, batch_size=128)  # warm-up: admit all
        st1 = tr.hot_tier.stats()
        assert st1["misses"] > 0  # the cold fills happened
        client.reset_op_counts()
        out = tr.train_from_dataset(ds, batch_size=128)  # warm epoch
        counts = client.reset_op_counts()
        assert counts == {}, f"warm epoch performed PS RPCs: {counts}"
        st2 = out["hot_tier"]  # counters are tier-lifetime cumulative
        assert st2["misses"] == st1["misses"], "warm epoch missed"
        assert st2["hits"] > st1["hits"]
        assert st2["cold_fetches"] == st1["cold_fetches"]
        comm.stop()
    finally:
        client.close()
        for s in servers:
            s.stop()


def _sharded_against_single_chip(acc=None, single_chip_banks=None):
    """8-shard GSPMD mesh tier (replicated dynamic map, one bank a shard,
    all_to_all routed rows, the owner's ``cache_push`` on its bank block)
    trains to the single-chip tier's results. Dense grads psum over the
    mesh (association differs from the serial sum), so this pins a tight
    tolerance, not bits — within-mesh routed≡gathered bitwise parity is
    pinned by test_sharded_cache.py. With the SAME eight banks on the
    single chip a bank is a placement unit: every key gets the same row
    in both tiers."""
    ds = make_data(n=512, nid=60)
    mesh = mesh_mod.make_mesh({"ps": 8})
    table = lambda: MemorySparseTable(TableConfig(  # noqa: E731
        shard_num=4, accessor="ctr", accessor_config=acc))
    ta = table()
    a = make_trainer(ta, HotTierConfig(capacity=512, banks=single_chip_banks))
    ra = a.train_from_dataset(ds, batch_size=128)
    tb = table()
    b = make_trainer(tb, HotTierConfig(capacity=512, mesh=mesh, axis="ps"))
    rb = b.train_from_dataset(ds, batch_size=128)
    assert rb["hot_tier"]["shards"] == 8 and rb["hot_tier"]["banks"] == 8
    if single_chip_banks == 8:
        keys = a.hot_tier.resident_keys()
        np.testing.assert_array_equal(
            np.sort(keys), np.sort(b.hot_tier.resident_keys()))
        np.testing.assert_array_equal(
            a.hot_tier.device_map.lookup_host(keys),
            b.hot_tier.device_map.lookup_host(keys))
    a.hot_tier.flush()
    b.hot_tier.flush()
    assert abs(ra["loss"] - rb["loss"]) < 1e-6
    for x, y in zip(_leaves(a.params), _leaves(b.params)):
        np.testing.assert_allclose(x, y, rtol=0, atol=1e-6)
    ka, va = _sorted_items(ta)
    kb, vb = _sorted_items(tb)
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_allclose(va, vb, rtol=0, atol=1e-6)


def test_hot_tier_sharded_mesh_step_matches_single_chip():
    _sharded_against_single_chip()


def test_hot_tier_stats_and_drop():
    """Observability counters (satellite) + drop() semantics."""
    table = MemorySparseTable(TableConfig(shard_num=2, accessor="ctr"))
    tier = HotEmbeddingTier(table, HotTierConfig(capacity=32))
    keys = np.asarray([1, 2, 3, 2, 1], np.uint64)
    tier.ensure(keys)
    st = tier.stats()
    # hit/miss counts are per-occurrence of the PRE-ensure resident set:
    # all five occurrences missed (the batch was fully cold)
    assert st["misses"] == 5 and st["hits"] == 0
    assert st["occupancy"] == 3 and st["dirty"] == 3
    assert st["capacity"] == 32 and st["hit_rate"] == 0.0
    n = tier.flush()
    assert n == 3 and tier.stats()["dirty"] == 0
    tier.ensure(keys)
    assert tier.stats()["hits"] == 5  # all resident now
    tier.drop()
    st = tier.stats()
    assert st["occupancy"] == 0 and st["dirty"] == 0
    # refill on miss after drop
    tier.ensure(keys)
    assert tier.stats()["occupancy"] == 3


# ---------------------------------------------------------------------------
# the rule family and the banked layouts at tier level: the REAL compiled
# steps against the RPC-only oracle and against each other
# ---------------------------------------------------------------------------


def _rule_parity(embed_rule, embedx_rule):
    """A tier whose accessor runs ``embed_rule`` / ``embedx_rule`` trains
    to the RPC-only oracle's bits: dense params, and after ``flush()``
    every save column but the per-flush delta_score. The embedx block is
    created on a row's first push (threshold 0, initial_range 0: the
    bit-parity preconditions of OPERATIONS.md section 5d), so the embedx
    rule's state round-trips the write-back too."""
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.sgd_rule import SGDRuleConfig

    acc = AccessorConfig(embed_sgd_rule=embed_rule,
                         embedx_sgd_rule=embedx_rule, embedx_threshold=0.0,
                         sgd=SGDRuleConfig(initial_range=0.0))
    ds = make_data(nid=120)
    ta = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr",
                                       accessor_config=acc))
    a = make_trainer(ta)
    a.train_from_dataset(ds, batch_size=64)
    tb = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr",
                                       accessor_config=acc))
    # 224 rows under ~330 distinct keys: evictions write state back
    b = make_trainer(tb, hot=HotTierConfig(capacity=224))
    rb = b.train_from_dataset(ds, batch_size=64)
    b.hot_tier.flush()
    assert rb["hot_tier"]["evictions"] > 0
    _assert_bitwise_equal(_leaves(a.params), _leaves(b.params))
    _assert_rows_equal_mod_delta(ta, tb)
    _, rows = _sorted_items(tb)
    has_embedx = rows[:, 6 + rule_state_dim(embed_rule, 1)]  # save layout
    assert (has_embedx == 1.0).all()


def test_hot_tier_pallas_adam_rule_parity():
    """An adam/adam accessor trains bit-identically through the tier
    (m/v moments and beta powers round-trip the writeback)."""
    _rule_parity("adam", "adam")


@pytest.mark.parametrize("embed_rule,embedx_rule",
                         [("naive", "naive"), ("std_adagrad", "std_adagrad"),
                          ("adagrad", "adam")],
                         ids=["naive", "std_adagrad", "adagrad+adam"])
def test_hot_tier_rule_parity(embed_rule, embedx_rule):
    """The rest of the rule family through the tier: zero-width state
    columns (naive), per-dimension state, and a mixed pair."""
    _rule_parity(embed_rule, embedx_rule)


def test_hot_tier_banked_single_chip_parity():
    """banks > 1 on a single chip (the NUMA bucket-per-bank layout)
    changes row PLACEMENT only: training results are bit-identical to
    the unbanked tier (ample capacity — no eviction-timing skew)."""
    ds = make_data(n=256, nid=60)
    ta = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    a = make_trainer(ta, hot=HotTierConfig(capacity=512))
    a.train_from_dataset(ds, batch_size=64)
    a.hot_tier.flush()
    tb = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    b = make_trainer(tb, hot=HotTierConfig(capacity=512, banks=4))
    rb = b.train_from_dataset(ds, batch_size=64)
    b.hot_tier.flush()
    assert rb["hot_tier"]["banks"] == 4
    _assert_bitwise_equal(_leaves(a.params), _leaves(b.params))
    ka, va = _sorted_items(ta)
    kb, vb = _sorted_items(tb)
    np.testing.assert_array_equal(ka, kb)
    np.testing.assert_array_equal(va, vb)


def test_hot_tier_sharded_banked_pallas_matches_jnp_bitwise():
    """The banked 8-shard tier against the single-chip tier with the same
    eight banks: same rows for the same keys, same results."""
    _sharded_against_single_chip(single_chip_banks=8)


def test_hot_tier_sharded_adam_matches_single_chip():
    """The routed owner-side push carrying adam's state: 4 columns on
    the embed weight, 18 on the embedx block."""
    from paddle_tpu.ps.accessor import AccessorConfig

    _sharded_against_single_chip(
        AccessorConfig(embed_sgd_rule="adam", embedx_sgd_rule="adam"),
        single_chip_banks=8)


def test_hot_tier_rejects_mismatched_embedx_dim():
    table = MemorySparseTable(TableConfig(shard_num=2, accessor="ctr"))
    pt.seed(0)
    with pytest.raises(Exception, match="embedx_dim"):
        CtrStreamTrainer(
            DeepFM(CtrConfig(num_sparse_slots=S, num_dense=D, embedx_dim=4,
                             dnn_hidden=(8,))),
            optimizer.Adam(1e-2), table, embedx_dim=4,
            sparse_slots=[f"s{i}" for i in range(S)],
            dense_slots=[f"d{i}" for i in range(D)], label_slot="label",
            hot_tier=HotEmbeddingTier(
                MemorySparseTable(TableConfig(shard_num=2, accessor="ctr")),
                HotTierConfig(capacity=32)))


def test_hot_tier_writebacks_route_fp32_under_int8_push_wire():
    """ISSUE 14 satellite pin: an int8 PUSH wire (push_wire_dtype) must
    not touch the tier's writeback path — dirty evictions/flushes ship
    as fp32 full-row import_full frames, so the tier stays BIT-identical
    to an fp32-wire RPC-only oracle even when the table config
    quantizes push_sparse. (An oracle pushing through the int8 wire
    would differ — that is the contract being pinned, not assumed.)"""
    servers = [rpc.NativePsServer(n_trainers=1) for _ in range(2)]
    eps = [f"127.0.0.1:{s.port}" for s in servers]
    servers_o = [rpc.NativePsServer(n_trainers=1) for _ in range(2)]
    eps_o = [f"127.0.0.1:{s.port}" for s in servers_o]
    cli = rpc.RpcPsClient(eps)
    cli_o = rpc.RpcPsClient(eps_o)
    try:
        # tier arm: table CONFIGURED for the quantized push wire; small
        # capacity forces eviction churn so dirty writebacks really flow
        cli.create_sparse_table(0, TableConfig(
            table_id=0, shard_num=4, accessor="ctr",
            push_wire_dtype="int8"))
        # SYNC communicator: the documented bit-parity precondition
        # (async oracle pulls are stale by queue depth — §5d)
        comm = SyncCommunicator(cli)
        comm.start()
        tr = make_trainer(None, hot=HotTierConfig(capacity=224),
                          communicator=comm)
        ds = make_data(nid=400)
        rb = tr.train_from_dataset(ds, batch_size=64)
        assert rb["hot_tier"]["writebacks"] > 0
        tr.hot_tier.flush()
        comm.stop()
        # oracle arm: plain fp32 wire, RPC-only
        cli_o.create_sparse_table(0, TableConfig(
            table_id=0, shard_num=4, accessor="ctr"))
        comm_o = SyncCommunicator(cli_o)
        comm_o.start()
        tr_o = make_trainer(None, communicator=comm_o)
        tr_o.train_from_dataset(ds, batch_size=64)
        comm_o.barrier()
        comm_o.stop()
        _assert_bitwise_equal(_leaves(tr.params), _leaves(tr_o.params))
        ka, va = cli.snapshot_items(0)
        kb, vb = cli_o.snapshot_items(0)
        ia, ib = np.argsort(ka), np.argsort(kb)
        np.testing.assert_array_equal(ka[ia], kb[ib])
        for c in range(va.shape[1]):
            if c == _DELTA_COL:
                continue
            np.testing.assert_array_equal(va[ia][:, c], vb[ib][:, c],
                                          err_msg=f"col {c}")
        # and the int8 wire config left ZERO residuals behind: the tier
        # never pushed through the quantized path at all
        assert cli.push_residual_rows() == 0
    finally:
        cli.close()
        cli_o.close()
        for s in servers + servers_o:
            s.stop()

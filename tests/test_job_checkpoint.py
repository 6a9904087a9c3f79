"""Crash-consistent unified job checkpointing (io/job_checkpoint.py).

Layers under test, bottom-up: the CRC32C primitive and durability
helpers (io/fs.py), the manifest/verify/fallback protocol over
dense-only checkpoints (no native toolchain needed), the save-path
faultpoints (torn writes are *scheduled*, not hoped-for), the
consistent-cut gate under concurrent PS traffic, trainer-integrated
checkpoint/resume bit-identity against an uninterrupted oracle, and THE
acceptance run — SIGKILL the whole job (trainers + in-process PS
cluster) mid-save in a subprocess, restart, resume from the newest
verified checkpoint with the newest published one deliberately
corrupted (checksum-detected fallback), final params bit-identical."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu.core.enforce import NotFoundError
from paddle_tpu.io import checkpoint as ckpt
from paddle_tpu.io.fs import crc32c, crc32c_file, publish_atomic
from paddle_tpu.io.job_checkpoint import (CorruptCheckpointError,
                                          JobCheckpointManager,
                                          combined_digest, verify_checkpoint)
from paddle_tpu.ps.faultpoints import (FaultInjected, arm_faultpoint,
                                       disarm_faultpoints)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _no_leaked_faults():
    yield
    disarm_faultpoints()


def _dense(seed=0):
    rng = np.random.default_rng(seed)
    return {"state": {"w": rng.normal(size=32).astype(np.float32),
                      "b": rng.normal(size=4).astype(np.float32)},
            "opt": {"m": rng.normal(size=32).astype(np.float32)}}


def _flip_byte(path, off=None):
    size = os.path.getsize(path)
    off = size // 2 if off is None else off
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0xFF]))


# ---------------------------------------------------------------------------
# CRC32C + durability primitives
# ---------------------------------------------------------------------------

def test_crc32c_known_vectors_and_chaining(tmp_path):
    assert crc32c(b"") == 0
    assert crc32c(b"123456789") == 0xE3069283  # the Castagnoli check word
    # RFC 3720 B.4: 32 zero bytes
    assert crc32c(bytes(32)) == 0x8A9136AA
    data = np.random.default_rng(0).integers(
        0, 256, 200_003, dtype=np.uint8).tobytes()
    one = crc32c(data)
    acc = 0
    for lo in range(0, len(data), 7001):  # chaining == one-shot
        acc = crc32c(data[lo:lo + 7001], acc)
    assert acc == one
    p = tmp_path / "blob"
    p.write_bytes(data)
    assert crc32c_file(str(p), chunk=4096) == one


def test_publish_atomic_directory(tmp_path):
    tmp = tmp_path / "stage.tmp"
    tmp.mkdir()
    (tmp / "a").write_text("payload")
    final = tmp_path / "published"
    publish_atomic(str(tmp), str(final))
    assert not tmp.exists() and (final / "a").read_text() == "payload"


# ---------------------------------------------------------------------------
# manifest / verify / corruption fallback (dense-only: no native needed)
# ---------------------------------------------------------------------------

def _mgr(tmp_path, **kw):
    return JobCheckpointManager(str(tmp_path / "ckpt"), **kw)


def _save_n(mgr, n, start=0):
    for i in range(start, start + n):
        mgr.save(step=i, cursor={"batch": i}, dense=_dense(i), blocking=True)


def test_save_load_roundtrip_and_manifest(tmp_path):
    mgr = _mgr(tmp_path)
    _save_n(mgr, 2)
    r = mgr.load_latest()
    assert r.step == 1 and r.cursor == {"batch": 1}
    want = _dense(1)
    np.testing.assert_array_equal(r.dense["state"]["w"], want["state"]["w"])
    np.testing.assert_array_equal(r.dense["opt"]["m"], want["opt"]["m"])
    man = verify_checkpoint(os.path.join(mgr.root, "ckpt_1"))
    assert man["step"] == 1 and man["dense"] is True
    assert set(man["artifacts"]) == {"dense.npz", "dense.meta.json"}
    mgr.stop()


def test_async_writer_publishes_and_latches_failures(tmp_path):
    mgr = _mgr(tmp_path)
    mgr.save(step=0, cursor={"batch": 0}, dense=_dense(0))
    mgr.wait()
    assert mgr.load_latest().step == 0
    # a write failure on the background thread surfaces at the NEXT
    # save (the communicator push-failure contract), never silently
    arm_faultpoint("ckpt.artifact", "drop-frame")
    mgr.save(step=1, cursor={"batch": 1}, dense=_dense(1))
    with pytest.raises(FaultInjected):
        mgr.wait()
    disarm_faultpoints()
    # the failed snapshot never published; the manager keeps working
    mgr.save(step=2, cursor={"batch": 2}, dense=_dense(2))
    mgr.stop()
    assert mgr.load_latest().step == 2


def test_truncated_artifact_falls_back(tmp_path):
    mgr = _mgr(tmp_path)
    _save_n(mgr, 2)
    # torn write: the crash landed between write and fsync
    path = os.path.join(mgr.root, "ckpt_1", "dense.npz")
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    r = mgr.load_latest()
    assert r.step == 0
    assert mgr.fallbacks and "truncated" in mgr.fallbacks[0][1]
    mgr.stop()


def test_bit_flipped_artifact_falls_back(tmp_path):
    mgr = _mgr(tmp_path)
    _save_n(mgr, 2)
    _flip_byte(os.path.join(mgr.root, "ckpt_1", "dense.npz"))
    r = mgr.load_latest()
    assert r.step == 0
    assert mgr.fallbacks and "CRC32C" in mgr.fallbacks[0][1]
    mgr.stop()


def test_missing_and_partial_manifest_fall_back(tmp_path):
    mgr = _mgr(tmp_path, max_keep=5)
    _save_n(mgr, 3)
    os.remove(os.path.join(mgr.root, "ckpt_2", "manifest.json"))
    with open(os.path.join(mgr.root, "ckpt_1", "manifest.json"),
              "r+") as f:  # torn mid-write: valid prefix, invalid JSON
        f.truncate(20)
    r = mgr.load_latest()
    assert r.step == 0
    reasons = dict(mgr.fallbacks)
    assert "missing" in reasons[2] and "unreadable" in reasons[1]
    mgr.stop()


def test_parseable_manifest_corruption_falls_back(tmp_path):
    """A flipped byte can leave manifest.json PARSEABLE — a cursor
    digit changes, every artifact CRC still verifies, and the job would
    silently resume at the wrong stream position. Only the manifest's
    own self-checksum catches this class."""
    mgr = _mgr(tmp_path, max_keep=5)
    _save_n(mgr, 2)
    mpath = os.path.join(mgr.root, "ckpt_1", "manifest.json")
    with open(mpath) as f:
        text = f.read()
    assert '"batch": 1' in text
    with open(mpath, "w") as f:
        f.write(text.replace('"batch": 1', '"batch": 9'))
    r = mgr.load_latest()
    assert r.step == 0
    assert mgr.fallbacks and "self-CRC32C" in mgr.fallbacks[0][1]
    # stripping the self-checksum entirely is corruption too, not a
    # downgrade to unchecked mode
    man = json.loads(text)
    del man["manifest_crc32c"]
    with open(mpath, "w") as f:
        json.dump(man, f)
    with pytest.raises(CorruptCheckpointError, match="self-checksum"):
        verify_checkpoint(os.path.join(mgr.root, "ckpt_1"))
    mgr.stop()


def test_no_verified_checkpoint_raises_notfound(tmp_path):
    mgr = _mgr(tmp_path)
    with pytest.raises(NotFoundError):
        mgr.load_latest()
    _save_n(mgr, 1)
    _flip_byte(os.path.join(mgr.root, "ckpt_0", "dense.npz"))
    with pytest.raises(NotFoundError):
        mgr.load_latest()
    with pytest.raises(CorruptCheckpointError):
        verify_checkpoint(os.path.join(mgr.root, "ckpt_0"))
    mgr.stop()


def test_faultpoint_truncate_and_flip_are_checksum_detected(tmp_path):
    """The armed save-path faults corrupt AFTER the checksum snapshot —
    exactly a torn write — so the verifier must catch them."""
    mgr = _mgr(tmp_path, max_keep=5)
    _save_n(mgr, 1)
    arm_faultpoint("ckpt.artifact", "truncate-artifact")
    _save_n(mgr, 1, start=1)   # publishes, but torn
    disarm_faultpoints()
    arm_faultpoint("ckpt.artifact", "flip-bytes")
    _save_n(mgr, 1, start=2)   # publishes, but bit-flipped
    disarm_faultpoints()
    r = mgr.load_latest()
    assert r.step == 0 and len(mgr.fallbacks) == 2
    mgr.stop()


def test_kill_before_publish_leaves_no_published_ckpt(tmp_path):
    """A crash before the os.replace (here: drop-frame at ckpt.publish)
    leaves only an unpublished .tmp — invisible to load, cleaned by the
    next manager."""
    mgr = _mgr(tmp_path)
    _save_n(mgr, 1)
    arm_faultpoint("ckpt.publish", "drop-frame")
    mgr.save(step=1, cursor={"batch": 1}, dense=_dense(1))
    with pytest.raises(FaultInjected):
        mgr.wait()
    disarm_faultpoints()
    assert mgr._ids() == [0]
    assert os.path.isdir(os.path.join(mgr.root, "ckpt_1.tmp"))
    assert mgr.load_latest().step == 0
    mgr.stop()
    mgr2 = JobCheckpointManager(mgr.root)   # restart: stale tmp cleared
    assert not os.path.exists(os.path.join(mgr.root, "ckpt_1.tmp"))
    assert mgr2._ids() == [0]
    mgr2.stop()


def test_gc_keeps_max_keep_newest(tmp_path):
    mgr = _mgr(tmp_path, max_keep=2)
    _save_n(mgr, 4)
    assert mgr._ids() == [2, 3]
    mgr.stop()


# ---------------------------------------------------------------------------
# sparse tables + gate (native toolchain)
# ---------------------------------------------------------------------------

rpc = pytest.importorskip("paddle_tpu.ps.rpc")

native_mark = pytest.mark.skipif(not rpc.rpc_available(),
                                 reason="native toolchain unavailable")

from paddle_tpu.ps import ha  # noqa: E402
from paddle_tpu.ps.accessor import AccessorConfig  # noqa: E402
from paddle_tpu.ps.sgd_rule import SGDRuleConfig  # noqa: E402
from paddle_tpu.ps.table import (MemorySparseTable, TableConfig,  # noqa: E402
                                 row_digest)


def _cfg():
    return TableConfig(shard_num=4, accessor_config=AccessorConfig(
        sgd=SGDRuleConfig(initial_range=0.0)))


@native_mark
def test_table_snapshot_restore_bit_exact(tmp_path):
    t = MemorySparseTable(_cfg())
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 4096, 700).astype(np.uint64)
    t.pull_sparse(keys, create=True)
    push = np.zeros((len(keys), 12), np.float32)
    push[:, 1] = 1.0
    push[:, 3:] = rng.normal(0, 0.1, (len(keys), 9)).astype(np.float32)
    t.push_sparse(keys, push)
    mgr = _mgr(tmp_path)
    mgr.register_sparse("ctr", t)
    mgr.save(step=1, dense=None, blocking=True)
    r = mgr.load_latest()
    fresh = MemorySparseTable(_cfg())
    n = r.restore_sparse("ctr", fresh)
    assert n == len(np.unique(keys))
    assert fresh.digest() == t.digest()   # bit-identical content
    # a corrupted restore target / drifted content is digest-detected
    bad = MemorySparseTable(_cfg())
    bad.pull_sparse(np.asarray([1 << 40], np.uint64), create=True)
    with pytest.raises(CorruptCheckpointError):
        r.restore_sparse("ctr", bad)
    mgr.stop()


@native_mark
def test_ssd_table_snapshot_restore_across_tiers(tmp_path):
    """Two-tier tables checkpoint through the same surface: snapshot
    covers hot + cold rows and the restored digest (sst_digest, both
    tiers) matches — this pinned a missing python binding for
    sst_digest found while driving the manager over SSD tables."""
    from paddle_tpu.ps.table import SsdSparseTable

    cfg = TableConfig(shard_num=4, storage="ssd",
                      accessor_config=AccessorConfig(
                          sgd=SGDRuleConfig(initial_range=0.0)))
    t = SsdSparseTable(str(tmp_path / "ssd_a"), cfg)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 30, 1500).astype(np.uint64)
    t.pull_sparse(keys, create=True)
    push = np.zeros((len(keys), 12), np.float32)
    push[:, 1] = 1.0
    push[:, 3:] = rng.normal(0, 0.1, (len(keys), 9)).astype(np.float32)
    t.push_sparse(keys, push)
    t.spill(300)   # most rows live in the cold tier at capture time
    mgr = _mgr(tmp_path)
    mgr.register_sparse("ssd", t)
    mgr.save(step=1, blocking=True)
    r = mgr.load_latest()
    fresh = SsdSparseTable(str(tmp_path / "ssd_b"), cfg)
    assert r.restore_sparse("ssd", fresh) == len(np.unique(keys))
    assert fresh.digest() == t.digest()
    mgr.stop()
    t.close()
    fresh.close()


@native_mark
def test_gate_cut_is_consistent_under_concurrent_pushes(tmp_path):
    """Captures taken while another client hammers pushes must be
    self-consistent: the manifest digest (taken under the gate) must
    equal the python row_digest of the arrays that were captured —
    a torn cut (rows moving mid-export) cannot hash equal."""
    import threading

    with ha.HACluster(num_shards=2, replication=2, sync=True) as cluster:
        cli = cluster.client()
        cli.create_sparse_table(0, _cfg())
        remote = rpc.RemoteSparseTable(cli, 0, _cfg())
        stop = threading.Event()
        rng = np.random.default_rng(1)

        def hammer():
            cli2 = cluster.client()
            r = np.random.default_rng(2)
            while not stop.is_set():
                ks = r.integers(0, 512, 64).astype(np.uint64)
                push = np.zeros((64, 12), np.float32)
                push[:, 1] = 1.0
                push[:, 3:] = r.normal(0, 0.1, (64, 9)).astype(np.float32)
                cli2.push_sparse(0, ks, push)

        seed_keys = rng.integers(0, 512, 256).astype(np.uint64)
        cli.pull_sparse(0, seed_keys, create=True)
        th = threading.Thread(target=hammer)
        th.start()
        try:
            mgr = _mgr(tmp_path, gate=cluster.checkpoint_gate(), max_keep=8)
            mgr.register_sparse("ctr", remote)
            for i in range(3):
                mgr.save(step=i, blocking=True)
        finally:
            stop.set()
            th.join()
        for no in mgr._ids():
            path = os.path.join(mgr.root, f"ckpt_{no}")
            man = verify_checkpoint(path)
            snap = ckpt.load(os.path.join(path, "sparse_ctr"))
            assert row_digest(
                np.ascontiguousarray(snap["keys"], np.uint64),
                np.ascontiguousarray(snap["values"], np.float32)) \
                == man["tables"]["ctr"]["digest"]
        assert mgr.stats()["pause_ms_last"] > 0.0
        mgr.stop()


# ---------------------------------------------------------------------------
# trainer-integrated resume: bit-identical to an uninterrupted oracle
# ---------------------------------------------------------------------------

def _make_stream_data(n=640, S=3, D=2, seed=0):
    from paddle_tpu.data.dataset import InMemoryDataset, SlotDesc

    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        ids = rng.integers(0, 48, S)
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


def _make_trainer(table, S=3, D=2):
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.models.ctr import CtrConfig, DeepFM
    from paddle_tpu.ps.ps_trainer import CtrStreamTrainer

    pt.seed(0)
    return CtrStreamTrainer(
        DeepFM(CtrConfig(num_sparse_slots=S, num_dense=D, embedx_dim=8,
                         dnn_hidden=(8,))),
        optimizer.Adam(1e-2), table, embedx_dim=8,
        sparse_slots=[f"s{i}" for i in range(S)],
        dense_slots=[f"d{i}" for i in range(D)], label_slot="label")


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


@native_mark
def test_stream_trainer_checkpoint_resume_bit_identical(tmp_path):
    """Local-table stream training with the in-loop checkpoint hook:
    restart from a mid-stream snapshot, replay the tail, and land
    BIT-identical (params, opt state, table digest) to a run that never
    stopped."""
    ds = _make_stream_data()

    oracle_tab = MemorySparseTable(_cfg())
    oracle = _make_trainer(oracle_tab)
    oracle.train_from_dataset(ds, batch_size=128)   # 5 batches

    job_tab = MemorySparseTable(_cfg())
    job = _make_trainer(job_tab)
    mgr = _mgr(tmp_path, max_keep=8)
    mgr.register_sparse("ctr", job_tab)
    job.train_from_dataset(ds, batch_size=128, checkpoint=mgr,
                           checkpoint_every=2)
    mgr.wait()

    # "restart": fresh table + trainer grafted from the batch-4 snapshot
    restored = mgr.load_latest()
    assert restored.cursor["batch"] == 4
    fresh_tab = MemorySparseTable(_cfg())
    resumed = _make_trainer(fresh_tab)
    restored.restore_sparse("ctr", fresh_tab)
    resumed.restore_train_state(restored.dense)
    # resume with the cursor DICT: a mismatched batch_size is a wrong
    # RECORD offset and must be rejected, not silently retrained
    with pytest.raises(Exception, match="record offset"):
        resumed.train_from_dataset(ds, batch_size=64,
                                   start_batch=restored.cursor)
    out = resumed.train_from_dataset(ds, batch_size=128,
                                     start_batch=restored.cursor)
    assert out["steps"] == 1.0   # only the tail replayed
    assert fresh_tab.digest() == oracle_tab.digest()
    for a, b in zip(_leaves(resumed.params), _leaves(oracle.params)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(resumed.opt_state), _leaves(oracle.opt_state)):
        np.testing.assert_array_equal(a, b)
    mgr.stop()


# ---------------------------------------------------------------------------
# THE acceptance run: SIGKILL the whole job mid-save, restart, resume
# ---------------------------------------------------------------------------

_JOB_SCRIPT = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddle_tpu as pt
from paddle_tpu import optimizer
from paddle_tpu.data.dataset import InMemoryDataset, SlotDesc
from paddle_tpu.io import checkpoint as ckpt
from paddle_tpu.io.job_checkpoint import JobCheckpointManager
from paddle_tpu.models.ctr import CtrConfig, DeepFM
from paddle_tpu.ps import ha, rpc
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.communicator import SyncCommunicator
from paddle_tpu.ps.faultpoints import arm_faultpoint
from paddle_tpu.ps.ps_trainer import CtrStreamTrainer
from paddle_tpu.ps.sgd_rule import SGDRuleConfig
from paddle_tpu.ps.table import TableConfig

phase, root, out = sys.argv[1], sys.argv[2], sys.argv[3]
S, D, B, ROWS = 3, 2, 128, 640
rng = np.random.default_rng(0)
lines = []
for _ in range(ROWS):
    ids = rng.integers(0, 48, S)
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
cfg = TableConfig(shard_num=4, accessor_config=AccessorConfig(
    sgd=SGDRuleConfig(initial_range=0.0)))

with ha.HACluster(num_shards=2, replication=2, sync=True) as cluster:
    cli = cluster.client()
    cli.create_sparse_table(0, cfg)
    comm = SyncCommunicator(cli)
    comm.start()
    pt.seed(0)
    tr = CtrStreamTrainer(
        DeepFM(CtrConfig(num_sparse_slots=S, num_dense=D, embedx_dim=8,
                         dnn_hidden=(8,))),
        optimizer.Adam(1e-2), None, communicator=comm, table_id=0,
        embedx_dim=8, sparse_slots=[f"s{i}" for i in range(S)],
        dense_slots=[f"d{i}" for i in range(D)], label_slot="label")
    remote = rpc.RemoteSparseTable(cli, 0, cfg)
    if phase == "oracle":
        tr.train_from_dataset(ds, batch_size=B)
    elif phase == "victim":
        # die by SIGKILL during the THIRD checkpoint's manifest write:
        # ckpt 0 and 1 publish fully, ckpt 2 is torn mid-save — the
        # whole job (trainer + both PS shards + coordinator) vanishes
        arm_faultpoint("ckpt.manifest", "kill-job", after=3)
        mgr = JobCheckpointManager(root, gate=cluster.checkpoint_gate(),
                                   max_keep=10)
        mgr.register_sparse("ctr", remote)
        tr.train_from_dataset(ds, batch_size=B, checkpoint=mgr,
                              checkpoint_every=1)
        mgr.stop()   # drains the writer: the armed kill MUST fire
        print("SURVIVED", flush=True)   # unreachable
        sys.exit(3)
    elif phase == "resume":
        mgr = JobCheckpointManager(root, gate=cluster.checkpoint_gate(),
                                   max_keep=10)
        mgr.register_sparse("ctr", remote)
        r = mgr.load_latest()
        r.restore_sparse("ctr", remote)
        tr.restore_train_state(r.dense)
        tr.train_from_dataset(ds, batch_size=B,
                              start_batch=r.cursor)
        print("META", r.ckpt_id, r.cursor["batch"], len(mgr.fallbacks),
              flush=True)
        mgr.stop()
    comm.stop()
    probe = np.unique(
        (np.arange(0, 48, dtype=np.uint64)[None, :]
         + (np.arange(S, dtype=np.uint64)[:, None] << np.uint64(32)))
        .reshape(-1))
    pulled = cli.pull_sparse(0, probe, create=False)
    ckpt.save({"pulled": pulled, "params": tr.params,
               "opt": tr.opt_state}, out)
print("DONE", flush=True)
"""


def _run_job(phase, root, out, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-c", _JOB_SCRIPT, phase, str(root), str(out)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=timeout)


@native_mark
@pytest.mark.slow
def test_job_sigkill_mid_save_resume_bit_identical(tmp_path):
    """E2E acceptance: SIGKILL the full job mid-save during
    CtrStreamTrainer training, corrupt the newest PUBLISHED checkpoint
    on top, restart — load falls back to the previous verified snapshot
    (checksum-detected) and the resumed run's final params/opt/table
    rows are BIT-identical to a fault-free oracle."""
    root = tmp_path / "jobckpt"
    oracle_out = tmp_path / "oracle"
    resume_out = tmp_path / "resume"

    p = _run_job("oracle", root, oracle_out)
    assert p.returncode == 0 and "DONE" in p.stdout, p.stdout + p.stderr

    p = _run_job("victim", root, tmp_path / "victim")
    assert p.returncode == -9, (p.returncode, p.stdout, p.stderr)  # SIGKILL
    assert "SURVIVED" not in p.stdout
    ids = sorted(int(d.split("_")[1]) for d in os.listdir(root)
                 if d.startswith("ckpt_") and not d.endswith(".tmp"))
    assert ids == [0, 1]   # ckpt 2 died unpublished

    # deliberately corrupt the newest PUBLISHED checkpoint: the restart
    # must detect it via checksums and fall back to ckpt_0
    _flip_byte(os.path.join(root, "ckpt_1", "sparse_ctr.npz"))

    p = _run_job("resume", root, resume_out)
    assert p.returncode == 0 and "DONE" in p.stdout, p.stdout + p.stderr
    meta = [l for l in p.stdout.splitlines() if l.startswith("META")][0]
    _, ckpt_id, cursor, fallbacks = meta.split()
    assert (int(ckpt_id), int(cursor), int(fallbacks)) == (0, 1, 1)

    want = ckpt.load(str(oracle_out))
    got = ckpt.load(str(resume_out))
    np.testing.assert_array_equal(got["pulled"], want["pulled"])
    for a, b in zip(_leaves(got["params"]), _leaves(want["params"])):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(_leaves(got["opt"]), _leaves(want["opt"])):
        np.testing.assert_array_equal(a, b)


def test_backpressured_save_does_not_hold_lifecycle_lock(tmp_path):
    """Regression (py_locks blocking-under-lock): a save() parked on a
    FULL writer queue must not hold _mu — other savers' admission/id
    allocation and stop() stay responsive while it waits, and stop()
    still orders its shutdown sentinel BEHIND every admitted
    snapshot."""
    import threading

    mgr = _mgr(tmp_path, queue_depth=1)
    release = threading.Event()
    wrote = []
    real_write = mgr._write

    def slow_write(snap):
        release.wait(20)
        real_write(snap)
        wrote.append(snap.ckpt_id)

    mgr._write = slow_write
    captured = []
    real_capture = mgr._capture

    def counting_capture(*a, **k):
        snap = real_capture(*a, **k)
        captured.append(snap.ckpt_id)
        return snap

    mgr._capture = counting_capture
    # writer busy on snap 0; snap 1 fills the queue; snap 2 must park
    # on the bounded put — formerly while holding _mu
    mgr.save(step=0, dense=_dense(0))
    t2 = threading.Thread(
        target=lambda: [mgr.save(step=1, dense=_dense(1)),
                        mgr.save(step=2, dense=_dense(2))],
        name="ckpt-producer")
    t2.start()
    # wait until snap 2 is ADMITTED (captured, counted in flight): a
    # full queue alone says only that snap 1 landed, and a stop() that
    # wins the race to snap 2's admission rightly refuses it
    deadline = time.perf_counter() + 10
    while not (len(captured) == 3 and mgr._inflight == 1) \
            and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert len(captured) == 3 and mgr._inflight == 1 and mgr._wq.full()
    # the lifecycle lock must be FREE while the producer is parked
    got_mu = mgr._mu.acquire(timeout=2)
    assert got_mu, "_mu held through a backpressured queue put"
    mgr._mu.release()
    # stop() (concurrent with the parked producer) must not deadlock
    # and must write everything that was admitted
    stopper = threading.Thread(target=mgr.stop, name="ckpt-stopper")
    stopper.start()
    time.sleep(0.05)
    release.set()
    t2.join(timeout=20)
    stopper.join(timeout=20)
    assert not t2.is_alive() and not stopper.is_alive()
    assert wrote == [0, 1, 2]          # FIFO, nothing behind the sentinel
    assert mgr.load_latest().step == 2

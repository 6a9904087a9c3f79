"""PS transport robustness: timeouts, bounded retry, reconnect and
failover when servers die mid-training.

Reference counterpart: the brpc client's FLAGS_pserver_* deadline/retry
family (brpc_ps_client.cc:24-45) and the elastic manager's expectation
that a dead pserver surfaces as a clean, bounded error rather than a
hang (fleet/elastic/manager.py).
"""

import os
import socket
import subprocess
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.sgd_rule import SGDRuleConfig
from paddle_tpu.ps.table import TableConfig

rpc = pytest.importorskip("paddle_tpu.ps.rpc")

pytestmark = pytest.mark.skipif(
    not rpc.rpc_available(), reason="native toolchain unavailable")

_SERVER_SCRIPT = """
import sys
import time
from paddle_tpu.ps.rpc import NativePsServer
s = NativePsServer(port=int(sys.argv[1]), n_trainers=1)
print("READY", s.port, flush=True)
time.sleep(3600)
"""


def _acc():
    return AccessorConfig(sgd=SGDRuleConfig(initial_range=0.0))


def _spawn_server(port=0):
    p = subprocess.Popen([sys.executable, "-c", _SERVER_SCRIPT, str(port)],
                         stdout=subprocess.PIPE, text=True, cwd=_REPO_ROOT)
    line = p.stdout.readline().strip()
    assert line.startswith("READY"), line
    return p, int(line.split()[1])


@pytest.fixture
def fast_flags():
    """Short deadlines so failure paths stay test-sized; restored after."""
    saved = pt.get_flags(["pserver_connect_timeout_ms", "pserver_timeout_ms",
                          "pserver_max_retry", "pserver_retry_backoff_ms",
                          "pserver_long_call_timeout_ms",
                          "pserver_barrier_timeout_ms"])
    pt.set_flags({"pserver_connect_timeout_ms": 1000,
                  "pserver_timeout_ms": 800,
                  "pserver_max_retry": 2,
                  "pserver_retry_backoff_ms": 20,
                  "pserver_long_call_timeout_ms": 1500,
                  "pserver_barrier_timeout_ms": 2000})
    yield
    pt.set_flags(saved)


def test_kill_server_mid_training_raises_bounded(fast_flags):
    """SIGKILL a live server mid-training: the next call fails with a
    clean PreconditionNotMetError naming the endpoint, within the
    retry×timeout budget — never a hang, never a wedged trainer."""
    proc, port = _spawn_server()
    try:
        cli = rpc.RpcPsClient([f"127.0.0.1:{port}"])
        cli.create_sparse_table(0, TableConfig(shard_num=4,
                                               accessor_config=_acc()))
        keys = np.arange(1, 64, dtype=np.uint64)
        assert (cli.pull_sparse(0, keys) == 0).all()  # training under way

        proc.kill()
        proc.wait()
        t0 = time.monotonic()
        with pytest.raises(Exception, match="unreachable|refused|reset"):
            cli.pull_sparse(0, keys)
        elapsed = time.monotonic() - t0
        # 2 attempts × (≤1s connect) + backoff — well under the 30s the
        # old transport would hang for (forever, on a half-open peer)
        assert elapsed < 10, elapsed
        cli.close()
    finally:
        if proc.poll() is None:
            proc.kill()


def test_unresponsive_server_call_times_out(fast_flags):
    """A server that accepts but never answers (wedged host) trips the
    per-call IO deadline instead of blocking the trainer forever."""
    lst = socket.socket()
    lst.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lst.bind(("127.0.0.1", 0))
    lst.listen(4)
    port = lst.getsockname()[1]
    accepted = []
    import threading

    def sink():
        try:
            while True:
                c, _ = lst.accept()
                accepted.append(c)  # read nothing, answer nothing
        except OSError:
            pass

    th = threading.Thread(target=sink, daemon=True)
    th.start()
    try:
        cli = rpc.RpcPsClient([f"127.0.0.1:{port}"])
        t0 = time.monotonic()
        with pytest.raises(Exception, match="unreachable|timed out"):
            cli.create_sparse_table(0, TableConfig(shard_num=4,
                                                   accessor_config=_acc()))
        elapsed = time.monotonic() - t0
        assert elapsed < 10, elapsed  # 2 × 0.8s deadline + backoff
        cli.close()
    finally:
        lst.close()
        for c in accepted:
            c.close()


def test_barrier_deadline_is_finite(fast_flags):
    """A barrier against a world that never completes (peer died before
    arriving) trips the generous-but-finite barrier deadline instead of
    wedging the trainer forever."""
    lib = rpc._rpc_lib()
    h = lib.pss_create(0, 2)  # 2-trainer barrier; only 1 will arrive
    port = int(lib.pss_port(h))
    try:
        cli = rpc.RpcPsClient([f"127.0.0.1:{port}"])
        t0 = time.monotonic()
        with pytest.raises(Exception, match="unreachable|timed out"):
            cli.barrier()
        assert 1.0 < time.monotonic() - t0 < 10
        cli.close()
    finally:
        lib.pss_destroy(h)


def test_barrier_timeout_cancels_arrival(fast_flags):
    """A trainer whose barrier timed out must NOT leave a phantom
    arrival: the server cancels the count when the waiter's connection
    drops, so the next generation still requires every live trainer."""
    import threading

    lib = rpc._rpc_lib()
    h = lib.pss_create(0, 2)
    port = int(lib.pss_port(h))
    try:
        a = rpc.RpcPsClient([f"127.0.0.1:{port}"])
        with pytest.raises(Exception, match="unreachable|timed out"):
            a.barrier()  # arrives alone, times out, disconnects
        a.close()
        time.sleep(0.3)  # let the server notice the hangup and cancel

        b = rpc.RpcPsClient([f"127.0.0.1:{port}"])
        c = rpc.RpcPsClient([f"127.0.0.1:{port}"])
        released = []

        def arrive(cli, tag):
            cli.barrier()
            released.append(tag)

        tb = threading.Thread(target=arrive, args=(b, "b"), daemon=True)
        tb.start()
        time.sleep(0.7)
        # with a phantom arrival counted, b alone would have released
        assert released == [], "barrier released with a phantom arrival"
        tc = threading.Thread(target=arrive, args=(c, "c"), daemon=True)
        tc.start()
        tb.join(5)
        tc.join(5)
        assert sorted(released) == ["b", "c"]
        b.close()
        c.close()
    finally:
        lib.pss_destroy(h)


def test_bulk_load_survives_server_crash_and_replay(fast_flags, tmp_path):
    """The 1e9-path crash story: SIGKILL a server mid-bulk-load, restart
    it on the same SSD directories (cold-tier log replay), re-issue the
    failed chunk AND 1000 rows the server had already applied (client
    retries are at-least-once — duplicate appends are benign: the index
    keeps the newest record, compaction reclaims the garbage) and finish
    the load; every row is present with the right values and compact()
    shrinks the log back."""
    import paddle_tpu.ps.rpc as _rpc
    from paddle_tpu.ps.accessor import AccessorConfig

    proc, port = _spawn_server()
    cli = None
    acc = AccessorConfig(embedx_dim=4, embedx_threshold=0.0,
                         sgd=SGDRuleConfig(initial_range=0.0))
    cfg = TableConfig(shard_num=4, accessor_config=acc, storage="ssd",
                      ssd_path=str(tmp_path / "tiers"))
    # this test's own deadlines (fast_flags restores them): a load, a
    # replay or a stats call that a busy host slows down must finish, not
    # time out and be retried — a retry whose first attempt is still
    # applying server-side makes the counts below a race. The duplicate
    # this test is about is sent explicitly (``overlap``). The dead
    # server still fails fast: its port refuses the connection.
    pt.set_flags({"pserver_timeout_ms": 60_000,
                  "pserver_long_call_timeout_ms": 300_000,
                  "pserver_max_retry": 4})
    try:
        cli = _rpc.RpcPsClient([f"127.0.0.1:{port}"])
        cli.create_sparse_table(0, cfg)
        full_dim = cli._dims(0)[2]
        rng = np.random.default_rng(7)
        n = 30_000
        keys = np.arange(1, n + 1, dtype=np.uint64)
        vals = np.zeros((n, full_dim), np.float32)
        vals[:, 3] = 1.0
        vals[:, 5] = rng.normal(0, 0.01, n).astype(np.float32)

        half = n // 2
        assert cli.load_cold(0, keys[:half], vals[:half]) == half
        proc.kill()
        proc.wait()
        with pytest.raises(Exception, match="unreachable"):
            cli.load_cold(0, keys[half:], vals[half:])

        # restart on the SAME directories: the cold log replays
        proc, port2 = _spawn_server(port)
        assert port2 == port
        cli.create_sparse_table(0, cfg)
        st = cli.table_stats(0)
        assert st["cold_rows"] == half  # replayed, nothing lost
        # at-least-once retry: re-issue the whole failed chunk PLUS an
        # overlap of already-loaded rows (a retried frame the server
        # had actually applied before dying): THE duplicate
        overlap = keys[half - 1000 : half]
        assert cli.load_cold(0, np.concatenate([overlap, keys[half:]]),
                             np.concatenate([vals[half - 1000 : half],
                                             vals[half:]])) == n - half + 1000
        st = cli.table_stats(0)
        assert st["cold_rows"] == n  # duplicates shadowed, not counted
        sample = rng.choice(keys, 500, replace=False)
        got, found = cli.export_full(0, sample)
        assert found.all()
        np.testing.assert_allclose(got, vals[sample.astype(np.int64) - 1],
                                   atol=1e-6)
        disk_before = cli.table_stats(0)["disk_bytes"]
        cli.compact(0)
        st2 = cli.table_stats(0)
        assert st2["disk_bytes"] < disk_before  # garbage reclaimed
        # export_full PROMOTED the sampled rows to the hot tier (the
        # documented tier protocol) — the invariant is total rows, not
        # cold rows
        assert st2["hot_rows"] + st2["cold_rows"] == n
        assert st2["hot_rows"] == len(sample)
    finally:
        if cli is not None:
            cli.close()
        if proc.poll() is None:
            proc.kill()


def test_failover_to_restarted_server(fast_flags):
    """Stretch goal: kill a server, restart it on the same port, and the
    SAME client object recovers via reconnect — re-create the table,
    reload the checkpoint, keep training (the elastic resume loop)."""
    proc, port = _spawn_server()
    cli = None
    try:
        cfg = TableConfig(shard_num=4, accessor_config=_acc())
        cli = rpc.RpcPsClient([f"127.0.0.1:{port}"])
        cli.create_sparse_table(0, cfg)
        keys = np.arange(1, 128, dtype=np.uint64)
        push = np.zeros((len(keys), 12), np.float32)
        push[:, 1] = 1.0
        push[:, 3:] = 0.25
        cli.pull_sparse(0, keys)
        cli.push_sparse(0, keys, push)
        before = cli.pull_sparse(0, keys, create=False)

        import tempfile

        with tempfile.TemporaryDirectory() as ckpt:
            cli.save(0, ckpt)

            proc.kill()
            proc.wait()
            with pytest.raises(Exception, match="unreachable"):
                cli.pull_sparse(0, keys, create=False)

            proc, port2 = _spawn_server(port)  # same endpoint comes back
            assert port2 == port
            # the client's retry loop reconnects transparently; state is
            # restored from the checkpoint (auto-checkpoint resume role)
            cli.create_sparse_table(0, cfg)
            cli.load(0, ckpt)
        after = cli.pull_sparse(0, keys, create=False)
        np.testing.assert_allclose(after, before, atol=1e-6)
        # and training continues
        cli.push_sparse(0, keys, push)
        assert cli.size(0) == len(keys)
    finally:
        if cli is not None:
            cli.close()
        if proc.poll() is None:
            proc.kill()

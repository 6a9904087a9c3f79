"""Billion-row distributed sparse-table composition (VERDICT r4 next #3).

Composes what the repo already ships — N ``NativePsServer`` SUBPROCESSES,
each owning an SSD-tiered shard (csrc/ssd_table.cc), a chunked
``load_cold`` bulk build over the TCP transport, ``RemoteSparseTable``
pass builds (BuildPull from remote shards, ps_gpu_wrapper.cc:299),
sustained training passes at a configurable hot fraction, a mode-0
server-side streaming save (gzip converter), and a full restart +
server-side reload with sampled value parity — at a population sized to
the reference's scale story (README.md:31-34: 1e11 features served by
N-server sharding, memory_sparse_table.h:53-56).

Population auto-sizes to the disk unless DIST_POP is set: the table's
log records plus the gzip'd checkpoint must BOTH fit, so
    pop = min(DIST_POP_CAP, free_bytes * 0.80 / (rec_bytes + save_bytes))
with save_bytes estimated from a measured small-scale save. Whatever is
chosen is recorded in the artifact ("largest that fits, stated").

Emits one JSON line (committed as DIST_SCALE.json). Knobs:
DIST_SERVERS (4), DIST_POP ("auto"), DIST_POP_CAP (1e9), DIST_DIM (4),
DIST_PASSES (3), DIST_PASS_KEYS (400k), DIST_HOT_FRACTION (0.02),
DIST_DIR (tmp), DIST_CHUNK (4M rows per load_cold wave),
DIST_CONVERTER (gzip | raw — the committed artifact used gzip; raw is
~6x faster at ~2x the bytes, see the save_local docstring).

Single-core host caveat: run ALONE in the foreground;
rates measured under concurrent load are garbage.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_SERVER = """
import sys
from paddle_tpu.ps.rpc import NativePsServer
import time
s = NativePsServer(port=0, n_trainers=1)
print("READY", s.port, flush=True)
while not s.stopped:
    time.sleep(0.2)
s.close()
"""


def _rss_bytes(pid="self") -> int:
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


def _du(path) -> int:
    total = 0
    for root, _, files in os.walk(path):
        for fn in files:
            try:
                total += os.path.getsize(os.path.join(root, fn))
            except OSError:
                pass
    return total


def spawn_servers(n):
    procs, ports = [], []
    for _ in range(n):
        p = subprocess.Popen([sys.executable, "-c", _SERVER],
                             stdout=subprocess.PIPE, text=True,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        line = p.stdout.readline().strip()
        assert line.startswith("READY"), line
        procs.append(p)
        ports.append(int(line.split()[1]))
    return procs, ports


def restore_only(ckpt: str) -> None:
    """Re-run ONLY the restore leg against an existing save_local
    checkpoint (DIST_RESTORE_ONLY=<ckpt_dir>): fresh server processes,
    fresh SSD directories, server-side load, parity against a sample
    PARSED FROM THE CHECKPOINT TEXT itself (ground truth travels in the
    artifact, so the original client's in-memory sample isn't needed).
    Exists because the first full run's restore leg hit the hash-order
    quadratic-probing bug — build/save/pass numbers from that run stand
    (they completed before the bug bit), and redoing 1.5 h of build to
    re-measure a 15-minute leg after the fix would say nothing new."""
    import gzip
    import json as _json

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu.ps.rpc as rpc
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.sgd_rule import SGDRuleConfig
    from paddle_tpu.ps.table import TableConfig, parse_shard_row

    n_servers = int(os.environ.get("DIST_SERVERS", 4))
    dim = int(os.environ.get("DIST_DIM", 4))
    base = os.environ.get("DIST_DIR") or tempfile.mkdtemp(prefix="dist_rest_")
    acc = AccessorConfig(embedx_dim=dim, embedx_threshold=0.0,
                         sgd=SGDRuleConfig(initial_range=0.0))
    with open(os.path.join(ckpt, "meta.json")) as f:
        meta = _json.load(f)
    assert meta["shard_num"] == n_servers, (meta, n_servers)

    # ground-truth sample: first K parseable lines of each shard file
    ed = 1  # adagrad embed state
    want = {}
    for s in range(n_servers):
        path = os.path.join(ckpt, f"part-{s:05d}.shard.gz")
        with gzip.open(path, "rt") as f:
            for _, line in zip(range(500), f):
                parts = line.split()
                if parts:
                    k, row = parse_shard_row(parts, ed, dim, 7 + ed + dim + 1)
                    want[int(k)] = row
    sample = np.asarray(sorted(want), np.uint64)

    out = {"mode": "restore_only", "ckpt": ckpt, "n_servers": n_servers,
           "host_cores": os.cpu_count()}
    procs, cli = [], None
    try:
        procs, ports = spawn_servers(n_servers)
        cli = rpc.RpcPsClient([f"127.0.0.1:{p}" for p in ports])
        cfg = TableConfig(shard_num=8, accessor_config=acc, storage="ssd",
                          ssd_path=os.path.join(base, "tiers_restore"))
        cli.create_sparse_table(0, cfg)
        t0 = time.perf_counter()
        restored = cli.load_local(0, ckpt)
        load_s = time.perf_counter() - t0
        got, found = cli.export_full(0, sample)
        expect = np.stack([want[int(k)] for k in sample])
        parity = bool(found.all()) and bool(
            np.allclose(got, expect, rtol=1e-6, atol=1e-9))
        out["restore"] = {"rows": int(restored), "seconds": round(load_s, 1),
                          "rows_per_s": round(restored / max(load_s, 1e-9)),
                          "sampled_parity": parity,
                          "sample_size": int(len(sample)),
                          "stats": cli.table_stats(0),
                          "server_rss": [_rss_bytes(p.pid) for p in procs]}
        out["ok"] = parity
    finally:
        try:
            if cli is not None:
                cli.stop_servers()
                cli.close()
        except Exception:
            pass
        for p in procs:
            if p.poll() is None:
                p.kill()
        shutil.rmtree(os.path.join(base, "tiers_restore"),
                      ignore_errors=True)
    print(json.dumps(out))


def main() -> None:
    if os.environ.get("DIST_RESTORE_ONLY"):
        restore_only(os.environ["DIST_RESTORE_ONLY"])
        return
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as pt
    import paddle_tpu.ps.rpc as rpc
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.sgd_rule import SGDRuleConfig
    from paddle_tpu.ps.table import TableConfig

    n_servers = int(os.environ.get("DIST_SERVERS", 4))
    dim = int(os.environ.get("DIST_DIM", 4))
    n_passes = int(os.environ.get("DIST_PASSES", 3))
    pass_keys = int(os.environ.get("DIST_PASS_KEYS", 400_000))
    hot_fraction = float(os.environ.get("DIST_HOT_FRACTION", 0.02))
    chunk = int(os.environ.get("DIST_CHUNK", 4_000_000))
    pop_cap = int(float(os.environ.get("DIST_POP_CAP", 1_000_000_000)))
    base = os.environ.get("DIST_DIR") or tempfile.mkdtemp(prefix="dist_scale_")
    cleanup = "DIST_DIR" not in os.environ
    os.makedirs(base, exist_ok=True)

    pt.seed(0)
    rng = np.random.default_rng(0)
    acc = AccessorConfig(embedx_dim=dim, embedx_threshold=0.0,
                         sgd=SGDRuleConfig(initial_range=0.0))

    out = {"n_servers": n_servers, "embedx_dim": dim,
           "host_cores": os.cpu_count()}
    procs, cli = [], None
    try:
        procs, ports = spawn_servers(n_servers)
        cli = rpc.RpcPsClient([f"127.0.0.1:{p}" for p in ports])
        cfg = TableConfig(shard_num=8, accessor_config=acc, storage="ssd",
                          ssd_path=os.path.join(base, "tiers_a"))
        cli.create_sparse_table(0, cfg)
        full_dim = cli._dims(0)[2]
        rec_bytes = 12 + 4 * full_dim
        out["full_dim"] = full_dim
        out["rec_bytes"] = rec_bytes

        def make_vals(keys):
            n = len(keys)
            vals = np.zeros((n, full_dim), np.float32)
            vals[:, 0] = keys % 26            # slot
            vals[:, 3] = 1.0                  # show
            vals[:, 5] = 0.01 * rng.standard_normal(n).astype(np.float32)
            vals[:, 7] = 1.0                  # has_embedx (ed=1 adagrad)
            vals[:, 8:8 + dim] = 0.01 * rng.standard_normal(
                (n, dim)).astype(np.float32)
            return vals

        # -- size the population to the disk --------------------------------
        pop_env = os.environ.get("DIST_POP", "auto")
        probe_n = 2_000_000
        keys = np.arange(1, probe_n + 1, dtype=np.uint64)
        t0 = time.perf_counter()
        assert cli.load_cold(0, keys, make_vals(keys), chunk=chunk) == probe_n
        probe_rate = probe_n / (time.perf_counter() - t0)
        t0 = time.perf_counter()
        conv = os.environ.get("DIST_CONVERTER", "gzip")
        saved = cli.save_local(0, os.path.join(base, "probe_ckpt"), mode=0,
                               converter=conv)
        probe_save_rate = saved / (time.perf_counter() - t0)
        save_bytes_row = _du(os.path.join(base, "probe_ckpt")) / max(saved, 1)
        shutil.rmtree(os.path.join(base, "probe_ckpt"))
        if pop_env == "auto":
            free = shutil.disk_usage(base).free
            pop = int(free * 0.80 / (rec_bytes + save_bytes_row))
            pop = min(pop, pop_cap)
        else:
            pop = int(float(pop_env))
        pop = max(pop, probe_n)
        out["population"] = pop
        out["sizing"] = {
            "free_bytes_at_start": shutil.disk_usage(base).free,
            "probe_load_rows_per_s": round(probe_rate),
            "probe_save_rows_per_s": round(probe_save_rate),
            "est_save_bytes_per_row": round(save_bytes_row, 1),
            "auto": pop_env == "auto",
        }

        # -- bulk build: the remaining population ---------------------------
        t0 = time.perf_counter()
        chunk_rates = []
        for lo in range(probe_n, pop, chunk):
            n = min(chunk, pop - lo)
            keys = np.arange(lo + 1, lo + 1 + n, dtype=np.uint64)
            tc = time.perf_counter()
            got = cli.load_cold(0, keys, make_vals(keys), chunk=chunk)
            assert got == n, (got, n)
            chunk_rates.append(n / (time.perf_counter() - tc))
        build_s = time.perf_counter() - t0
        st = cli.table_stats(0)
        out["build"] = {
            "rows": pop,
            "seconds": round(build_s, 1),
            "rows_per_s": round((pop - probe_n) / max(build_s, 1e-9)),
            "rate_first_chunk": round(chunk_rates[0]) if chunk_rates else None,
            "rate_last_chunk": round(chunk_rates[-1]) if chunk_rates else None,
            "cold_rows": st["cold_rows"],
            "disk_bytes": st["disk_bytes"],
            "client_rss": _rss_bytes(),
            "server_rss": [_rss_bytes(p.pid) for p in procs],
        }

        # -- sustained passes over a hot working set ------------------------
        from paddle_tpu.ps.rpc import RemoteSparseTable

        remote = RemoteSparseTable(cli, 0, cfg)
        from paddle_tpu import optimizer
        from paddle_tpu.models.ctr import (CtrConfig, DeepFM,
                                           make_ctr_train_step)
        from paddle_tpu.ps.embedding_cache import CacheConfig, HbmEmbeddingCache

        hot_pool = max(int(pop * hot_fraction), pass_keys)
        cap = 1 << int(np.ceil(np.log2(max(pass_keys * 1.25, 1 << 18))))
        cache = HbmEmbeddingCache(remote, CacheConfig(
            capacity=cap, embedx_dim=dim, embedx_threshold=0.0))
        ccfg = CtrConfig(num_sparse_slots=8, num_dense=4, embedx_dim=dim,
                         dnn_hidden=(64, 64))
        model = DeepFM(ccfg)
        opt = optimizer.Adam(1e-3)
        params = {"params": dict(model.named_parameters()), "buffers": {}}
        ostate = opt.init(params)
        step = make_ctr_train_step(model, opt, cache.config)
        passes = []
        for pno in range(n_passes):
            # hot keys cluster at the front of the id space + a cold tail
            hot = rng.integers(1, hot_pool + 1,
                               size=int(pass_keys * 0.9)).astype(np.uint64)
            tail = rng.integers(1, pop + 1,
                                size=pass_keys - len(hot)).astype(np.uint64)
            pk = np.concatenate([hot, tail]).reshape(-1, 8)
            t0 = time.perf_counter()
            n_uniq = cache.begin_pass(pk.reshape(-1))
            build_pass_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(20):
                b = rng.integers(0, pk.shape[0], size=512)
                rows = cache.lookup(pk[b].reshape(-1)).reshape(512, 8)
                dense = rng.standard_normal((512, 4)).astype(np.float32)
                lab = (pk[b, 0] % 2).astype(np.int32)
                params, ostate, cache.state, loss = step(
                    params, ostate, cache.state, rows, dense, lab)
            jax.block_until_ready(loss)
            steps_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            cache.end_pass()
            flush_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            spilled = cli.spill(0, hot_budget=hot_pool)
            spill_s = time.perf_counter() - t0
            passes.append({"uniq": int(n_uniq),
                           "build_pull_s": round(build_pass_s, 2),
                           "steps_s": round(steps_s, 2),
                           "flush_s": round(flush_s, 2),
                           "spill_s": round(spill_s, 2),
                           "spilled": int(spilled)})
        out["passes"] = passes
        out["after_passes_stats"] = cli.table_stats(0)

        # sample BEFORE save for post-restore parity
        sample = rng.choice(np.arange(1, pop + 1, dtype=np.uint64), 2000,
                            replace=False)
        want, found = cli.export_full(0, sample)
        assert found.all()

        # -- mode-0 save (server-side streaming, gzip) ----------------------
        ckpt = os.path.join(base, "ckpt")
        t0 = time.perf_counter()
        saved = cli.save_local(0, ckpt, mode=0, converter=conv)
        save_s = time.perf_counter() - t0
        out["save"] = {"rows": int(saved), "seconds": round(save_s, 1),
                       "rows_per_s": round(saved / max(save_s, 1e-9)),
                       "bytes": _du(ckpt),
                       "bytes_per_row": round(_du(ckpt) / max(saved, 1), 1)}

        # -- restart: fresh servers + fresh dirs + server-side reload -------
        cli.stop_servers()
        cli.close()
        cli = None
        for p in procs:
            p.wait(timeout=60)
        procs = []
        shutil.rmtree(os.path.join(base, "tiers_a"))

        procs, ports = spawn_servers(n_servers)
        cli = rpc.RpcPsClient([f"127.0.0.1:{p}" for p in ports])
        cfg_b = TableConfig(shard_num=8, accessor_config=acc, storage="ssd",
                            ssd_path=os.path.join(base, "tiers_b"))
        cli.create_sparse_table(0, cfg_b)
        t0 = time.perf_counter()
        restored = cli.load_local(0, ckpt)
        load_s = time.perf_counter() - t0
        got, found = cli.export_full(0, sample)
        parity = bool(found.all()) and bool(
            np.allclose(got, want, rtol=1e-6, atol=1e-9))
        out["restore"] = {"rows": int(restored), "seconds": round(load_s, 1),
                          "rows_per_s": round(restored / max(load_s, 1e-9)),
                          "sampled_parity": parity,
                          "stats": cli.table_stats(0)}
        out["ok"] = bool(parity and restored == saved)
    finally:
        try:
            if cli is not None:
                cli.stop_servers()
                cli.close()
        except Exception:
            pass
        for p in procs:
            if p.poll() is None:
                p.kill()
        if cleanup:
            shutil.rmtree(base, ignore_errors=True)
    print(json.dumps(out))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # noqa: BLE001 — artifact must be one JSON line
        import traceback

        traceback.print_exc(file=sys.stderr)
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:300]}))
        sys.exit(0)

"""Hot-tier vs RPC-only sparse-embedding bench (ROADMAP item 1 rung).

Identical seeded DeepFM streams train against a real 2-shard RPC PS
cluster (NativePsServer + RpcPsClient + HalfAsyncCommunicator — the
production transport, not a local table):

- **rpc_only** — every batch pulls/pushes over the RPC wire (the PR-2
  overlapped path);
- **hot_tier** — the persistent single-chip HBM tier (ps/hot_tier.py):
  after one admission epoch the working set is device-resident and the
  measured epoch's steps run entirely in-graph;
- **sharded** (the multi-host rung) — the banked multi-host tier on an
  8-device mesh (per-bank row blocks = per-shard HBM, ``all_to_all``
  id/vector exchange). Multi-device backends run it in-process; a
  1-device backend (the CPU CI rung) re-runs THIS script in a
  subprocess with 8 virtual CPU devices (the dense_comm_bench
  pattern). The sharded record also carries ``exchange_bytes``: the
  compiled step's collective wire bytes (tools/hlo_bytes.py) under the
  routed ``all_to_all`` formulation vs the gathered
  ``all_gather``+``reduce_scatter`` fallback — the proof that the
  routed exchange moves fewer bytes, independent of host timing noise.

All arms measure their SECOND epoch (compile warm, rows created — the
steady state the tier exists for) and report samples/sec, the per-step
PS RPC count (RpcPsClient.op_counts deltas — the hot-tier CI gate's
counter), and the tier's hit-rate/occupancy stats. The headline
``value`` is hot-tier samples/sec; ``speedup_vs_rpc_only`` and the
0-RPC claim ride the record for the CI full gate.

Standalone: prints exactly ONE JSON line (driver contract). Importable:
``run()`` returns the record. Env knobs: SHB_BATCH, SHB_SAMPLES,
SHB_NID, SHB_CAPACITY, SHB_SLOTS, SHB_SHARDED (0 skips the rung).
"""

import json
import os
import sys
import time

METRIC = "sparse_hot_samples_per_sec"
_CHILD_ENV = "SHB_ROLE"   # set to "sharded" in the 8-virtual-dev child


def _params():
    return {
        "S": int(os.environ.get("SHB_SLOTS", 8)),
        "D": 4,
        "batch": int(os.environ.get("SHB_BATCH", 256)),
        "n_samples": int(os.environ.get("SHB_SAMPLES", 4096)),
        "nid": int(os.environ.get("SHB_NID", 1500)),
        "capacity": int(os.environ.get("SHB_CAPACITY", 1 << 14)),
    }


def _dataset(p):
    import numpy as np

    from paddle_tpu.data.dataset import InMemoryDataset, SlotDesc

    S, D = p["S"], p["D"]
    rng = np.random.default_rng(0)
    lines = []
    for _ in range(p["n_samples"]):
        ids = rng.integers(0, p["nid"], S)
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


def _measure(p, ds, hot):
    """One arm: train two epochs against a real RPC PS cluster, time
    the second (warm) one. ``hot`` = HotTierConfig | None (rpc-only)."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.models.ctr import CtrConfig, DeepFM
    from paddle_tpu.ps import rpc
    from paddle_tpu.ps.communicator import HalfAsyncCommunicator
    from paddle_tpu.ps.ps_trainer import CtrStreamTrainer
    from paddle_tpu.ps.table import TableConfig

    S, D, batch = p["S"], p["D"], p["batch"]
    servers = [rpc.NativePsServer(n_trainers=1) for _ in range(2)]
    client = rpc.RpcPsClient([f"127.0.0.1:{s.port}" for s in servers])
    try:
        client.create_sparse_table(
            0, TableConfig(table_id=0, shard_num=4, accessor="ctr"))
        comm = HalfAsyncCommunicator(client)
        comm.start()
        pt.seed(0)
        tr = CtrStreamTrainer(
            DeepFM(CtrConfig(num_sparse_slots=S, num_dense=D,
                             embedx_dim=8, dnn_hidden=(64, 64))),
            optimizer.Adam(1e-2), None, embedx_dim=8,
            sparse_slots=[f"s{i}" for i in range(S)],
            dense_slots=[f"d{i}" for i in range(D)],
            label_slot="label", communicator=comm, table_id=0,
            hot_tier=hot)
        tr.train_from_dataset(ds, batch_size=batch)  # warm-up epoch
        pre = tr.hot_tier.stats() if hot is not None else None
        client.reset_op_counts()
        t0 = time.perf_counter()
        out = tr.train_from_dataset(ds, batch_size=batch)
        wall = time.perf_counter() - t0
        counts = client.reset_op_counts()
        comm.stop()
        steps = max(out["steps"], 1.0)
        rec = {
            # wall-clock rate, not the result dict's (which excludes
            # the trailing barrier drain the RPC path relies on)
            "samples_per_sec": round(out["samples"] / wall, 1),
            "rpc_per_step": round(sum(counts.values()) / steps, 3),
            "rpc_ops": dict(counts),
            "steps": int(steps),
        }
        if hot is not None:
            st = out["hot_tier"]
            total = ((st["hits"] - pre["hits"])
                     + (st["misses"] - pre["misses"]))
            rec["hit_rate"] = round(
                (st["hits"] - pre["hits"]) / max(total, 1), 4)
            rec["occupancy"] = st["occupancy"]
            rec["evictions"] = st["evictions"]
            rec["shards"] = st["shards"]
            rec["banks"] = st["banks"]
        return rec
    finally:
        client.close()
        for s in servers:
            s.stop()


def _exchange_bytes(p, mesh, routing):
    """Compile (don't run) the sharded hot step under ``routing`` and
    report its collective wire bytes from the optimized HLO — the
    timing-independent half of the multi-host claim."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    here = os.path.dirname(os.path.abspath(__file__))
    if here not in sys.path:
        sys.path.insert(0, here)
    import hlo_bytes

    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.models.ctr import CtrConfig, DeepFM
    from paddle_tpu.ps.hot_tier import HotEmbeddingTier, HotTierConfig
    from paddle_tpu.ps.table import MemorySparseTable, TableConfig
    from paddle_tpu.ps.hot_tier import make_sharded_hot_train_step

    S, D, batch = p["S"], p["D"], p["batch"]
    pt.seed(0)
    model = DeepFM(CtrConfig(num_sparse_slots=S, num_dense=D, embedx_dim=8,
                             dnn_hidden=(64, 64)))
    opt = optimizer.Adam(1e-2)
    table = MemorySparseTable(TableConfig(shard_num=4, accessor="ctr"))
    tier = HotEmbeddingTier(table, HotTierConfig(
        capacity=p["capacity"], mesh=mesh, axis="ps", routing=routing))
    step = make_sharded_hot_train_step(
        model, opt, tier.cache_config, mesh,
        slot_ids=np.arange(S), axis="ps", routing=routing, donate=False,
        probe_buckets=tier.device_map.probe_buckets,
        banks=tier.device_map.banks)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    opt_state = opt.init(params)
    lo32 = jnp.zeros((batch, S), jnp.uint32)
    dense = jnp.zeros((batch, D), jnp.float32)
    labels = jnp.zeros((batch,), jnp.int32)
    compiled = step.lower(params, opt_state, tier.state,
                          tier.device_map.device_state(), lo32, dense,
                          labels).compile()
    rep = hlo_bytes.report_compiled(compiled, num_devices=len(jax.devices()))
    by_op = rep["wire_bytes_by_op"]
    # the sparse id/vector exchange: a2a under routed, ag+rs gathered
    return {
        "routing": routing,
        "wire_bytes_by_op": {k: int(v) for k, v in by_op.items()},
        "exchange_bytes": int(by_op.get("all-to-all", 0)
                              + by_op.get("all-gather", 0)
                              + by_op.get("reduce-scatter", 0)),
    }


def _run_sharded(p):
    """The multi-host rung (needs ≥ 8 devices): measured sharded
    samples/s + compile-time exchange-byte proof for both routings."""
    import jax

    from paddle_tpu.core import mesh as mesh_mod
    from paddle_tpu.ps.hot_tier import HotTierConfig

    mesh = mesh_mod.make_mesh({"ps": 8})
    ds = _dataset(p)
    rec = _measure(p, ds, HotTierConfig(capacity=p["capacity"], mesh=mesh,
                                        axis="ps"))
    routed = _exchange_bytes(p, mesh, "alltoall")
    gathered = _exchange_bytes(p, mesh, "allgather")
    rec["exchange"] = {
        "alltoall": routed,
        "gathered": gathered,
        "alltoall_over_gathered": round(
            routed["exchange_bytes"] / max(gathered["exchange_bytes"], 1),
            4),
    }
    rec["devices"] = len(jax.devices())
    rec["platform"] = jax.devices()[0].platform
    return rec


def _sharded_rung(p):
    """In-process on a multi-device backend; otherwise a subprocess with
    8 virtual CPU devices."""
    if os.environ.get("SHB_SHARDED", "1") != "1":
        return None
    try:
        import jax

        if len(jax.devices()) >= 8:
            return _run_sharded(p)
        import subprocess

        env = dict(os.environ)
        env.update({
            _CHILD_ENV: "sharded",
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"
                          ).strip(),
        })
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=900)
        lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
        if not lines:
            # no JSON = the child died before the one-line contract —
            # surface ITS diagnostics, not an IndexError
            return {"error": f"sharded child rc={out.returncode}: "
                             + out.stderr.strip()[-300:]}
        return json.loads(lines[-1])
    except Exception as e:  # noqa: BLE001 — optional rung, never fatal
        return {"error": f"{type(e).__name__}: {e}"[:200]}


def run() -> dict:
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from paddle_tpu.ps.hot_tier import HotTierConfig

    p = _params()
    ds = _dataset(p)
    rpc_only = _measure(p, ds, None)
    hot = _measure(p, ds, HotTierConfig(capacity=p["capacity"]))
    sharded = _sharded_rung(p)

    out = {
        "metric": METRIC, "value": hot["samples_per_sec"],
        "unit": "samples/s", "hot_tier": hot, "rpc_only": rpc_only,
        "speedup_vs_rpc_only": round(
            hot["samples_per_sec"] / max(rpc_only["samples_per_sec"], 1e-9),
            3),
        "batch": p["batch"], "n_samples": p["n_samples"],
        "key_universe": p["nid"] * p["S"],
        "capacity": p["capacity"],
        "platform": jax.devices()[0].platform,
    }
    if sharded is not None:
        out["sharded"] = sharded
        if "samples_per_sec" in sharded:
            out["sharded_speedup_vs_rpc_only"] = round(
                sharded["samples_per_sec"]
                / max(rpc_only["samples_per_sec"], 1e-9), 3)
    return out


def main() -> None:
    try:
        if os.environ.get(_CHILD_ENV) == "sharded":
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            if repo not in sys.path:
                sys.path.insert(0, repo)
            rec = _run_sharded(_params())
        else:
            rec = run()
    except Exception as e:  # noqa: BLE001 — one-JSON-line contract
        import traceback

        traceback.print_exc(file=sys.stderr)
        rec = {"metric": METRIC, "value": 0.0,
               "error": f"{type(e).__name__}: {e}"[:300]}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()

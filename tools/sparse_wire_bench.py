"""Sparse push-wire ladder: fp32 vs fp16 vs int8 (ISSUE 14).

One seeded CTR push workload (merged-duplicate batches against a real
2-shard NativePsServer cluster) runs once per
``TableConfig.push_wire_dtype`` rung. Per rung the record carries:

- ``push_wire_bytes`` — the PR 8 per-table client byte counter's delta
  over the measured pushes (the counter measures the ENCODED payload,
  which is what the ≥3x CI gate asserts);
- ``bytes_per_row`` and ``samples_per_sec`` (host-loop push throughput
  — wall time on a shared CI box is indicative, the byte counts are
  exact);
- int8 additionally reports the residual rows drained at the end (the
  error-feedback store's quiesce contract).

Baseline-comparability note (the PR 12 lesson): every
ratio in this record is against THIS record's own fp32 rung — same
transport, same PR-2 overlapped client, same host. Ratios are not
comparable across records from different client eras; the committed
SPARSE_WIRE.json says which rpc baseline it measured.

Standalone: prints exactly ONE JSON line (driver contract).
Env knobs: SWB_ROWS, SWB_STEPS, SWB_EMBEDX, SWB_SHARDS.
"""

import json
import os
import sys
import time

METRIC = "sparse_push_wire_ratio_fp32_over_int8"


def _params():
    return {
        "rows": int(os.environ.get("SWB_ROWS", 4096)),
        "steps": int(os.environ.get("SWB_STEPS", 20)),
        "embedx": int(os.environ.get("SWB_EMBEDX", 64)),
        "shards": int(os.environ.get("SWB_SHARDS", 2)),
    }


def _push_bytes(table_id):
    from paddle_tpu.obs import registry as _reg

    snap = _reg.REGISTRY.snapshot()["metrics"]
    fam = snap.get("ps_client_wire_bytes", {"series": []})
    return sum(s["value"] for s in fam["series"]
               if s["labels"].get("dir") == "push"
               and s["labels"].get("table") == str(table_id))


def _run_rung(wire, p, tid):
    import numpy as np

    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.rpc import NativePsServer, RpcPsClient
    from paddle_tpu.ps.table import TableConfig

    srvs = [NativePsServer() for _ in range(p["shards"])]
    try:
        cli = RpcPsClient([f"127.0.0.1:{s.port}" for s in srvs])
        cli.create_sparse_table(tid, TableConfig(
            table_id=tid, push_wire_dtype=wire,
            accessor_config=AccessorConfig(embedx_dim=p["embedx"],
                                           embedx_threshold=0.0),
            seed=13))
        rng = np.random.default_rng(0)
        keys = rng.integers(1, 1 << 40, p["rows"]).astype(np.uint64)
        gd = 1 + p["embedx"]
        cli.pull_sparse(tid, keys)  # create rows outside the window
        before = _push_bytes(tid)
        t0 = time.perf_counter()
        for _ in range(p["steps"]):
            push = np.zeros((len(keys), 3 + gd), np.float32)
            push[:, 1] = 1.0
            push[:, 3:] = rng.normal(0, 0.1,
                                     (len(keys), gd)).astype(np.float32)
            cli.push_sparse(tid, keys, push)
        dt = time.perf_counter() - t0
        # steady-state wire FIRST; the error-feedback drain is a
        # checkpoint-boundary cost, not per-step wire — measured apart
        wire_bytes = _push_bytes(tid) - before
        drained = cli.drain_push_residuals(tid)
        drain_bytes = _push_bytes(tid) - before - wire_bytes
        n = p["rows"] * p["steps"]
        rec = {
            "wire": wire,
            "push_wire_bytes": int(wire_bytes),
            "bytes_per_row": round(wire_bytes / n, 2),
            "samples_per_sec": round(n / max(dt, 1e-9), 1),
            "residual_rows_drained": int(drained),
            "drain_bytes": int(drain_bytes),
        }
        cli.close()
        return rec
    finally:
        for s in srvs:
            s.stop()
            s.close()


def run():
    import jax

    p = _params()
    ladder = []
    for tid, wire in enumerate(("fp32", "fp16", "int8"), start=1):
        ladder.append(_run_rung(wire, p, tid))
    by = {r["wire"]: r for r in ladder}
    ratio = by["fp32"]["push_wire_bytes"] / max(
        by["int8"]["push_wire_bytes"], 1)
    return {
        "metric": METRIC,
        "value": round(ratio, 3),
        "ladder": ladder,
        "ratio_fp32_over_fp16": round(
            by["fp32"]["push_wire_bytes"]
            / max(by["fp16"]["push_wire_bytes"], 1), 3),
        # which baseline these ratios are against (the PR 12 lesson):
        # the SAME record's fp32 rung on the SAME PR-2 era client
        "baseline": "this-record fp32 rung (psc_callv scatter-gather "
                    "client, PR 2 era)",
        "rows": p["rows"], "steps": p["steps"], "embedx": p["embedx"],
        "shards": p["shards"],
        "platform": jax.devices()[0].platform,
    }


def main() -> None:
    try:
        rec = run()
    except Exception as e:  # noqa: BLE001 — one-JSON-line contract
        import traceback

        traceback.print_exc(file=sys.stderr)
        rec = {"metric": METRIC, "value": 0.0,
               "error": f"{type(e).__name__}: {e}"[:300]}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()

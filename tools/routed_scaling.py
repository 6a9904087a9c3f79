"""Measure the routed-serving scaling claim (VERDICT r2 #2) with
numbers: per-step wall time of sharded cache pull+push under the
key-routed all-to-all vs the dense all_gather fallback, across shard
counts, on the virtual CPU mesh.

The architectural claim: gathered serving does O(batch·K) work per
shard (every shard processes the whole global batch), routed serving
O(batch/K·cap_factor) — so as K grows, gathered per-step time grows
while routed stays ~flat. CPU devices share one host, so absolute
numbers are not TPU numbers, but the per-shard WORK ratio — the thing
the architecture changes — shows directly in the step time.

Writes ROUTED_SCALING.json. Env: RS_BATCH (512), RS_SLOTS (26),
RS_DIM (8), RS_STEPS (20), RS_SHARDS ("2,4,8").
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main() -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ps.embedding_cache import CacheConfig
    from paddle_tpu.ps.sharded_cache import (routed_cache_pull,
                                             routed_cache_push,
                                             routed_dedup,
                                             sharded_cache_pull,
                                             sharded_cache_push)

    B = int(os.environ.get("RS_BATCH", 512))
    S = int(os.environ.get("RS_SLOTS", 26))
    dim = int(os.environ.get("RS_DIM", 8))
    steps = int(os.environ.get("RS_STEPS", 20))
    shard_counts = [int(k) for k in
                    os.environ.get("RS_SHARDS", "2,4,8").split(",")]
    capacity = 1 << 18
    # RS_PUSH_MODE: "sparse" (default — the merge_grad shape, the
    # original artifact) or "dense" (the TPU hot path: per-shard
    # O(C/K) streaming — its cost FALLS as K grows)
    push_mode = os.environ.get("RS_PUSH_MODE", "sparse")
    cfg = CacheConfig(capacity=capacity, embedx_dim=dim,
                      embedx_threshold=0.0, push_mode=push_mode)
    rng = np.random.default_rng(0)
    devices = jax.devices()

    def fresh(cap_local, key):
        r = np.random.default_rng(key)
        return {
            "show": jnp.asarray(r.uniform(0, 5, cap_local).astype(np.float32)),
            "click": jnp.asarray(r.uniform(0, 2, cap_local).astype(np.float32)),
            "embed_w": jnp.asarray(r.normal(size=(cap_local, 1)).astype(np.float32)),
            "embed_state": jnp.asarray(r.uniform(0, 1, (cap_local, 1)).astype(np.float32)),
            "embedx_w": jnp.asarray(r.normal(size=(cap_local, dim)).astype(np.float32)),
            "embedx_state": jnp.asarray(r.uniform(0, 1, (cap_local, 1)).astype(np.float32)),
            "has_embedx": jnp.asarray((r.random(cap_local) < 0.5).astype(np.float32)),
        }

    out = {"batch": B, "slots": S, "dim": dim, "steps": steps,
           "capacity": capacity, "push_mode": push_mode, "modes": {}}
    m_global = B * S  # rows per step, total (each of K devices holds m/K)

    for routing in ("alltoall", "allgather"):
        res = {}
        for K in shard_counts:
            mesh = Mesh(np.array(devices[:K]), ("ps",))
            state = fresh(capacity, 0)
            shard = NamedSharding(mesh, P("ps"))
            ss = {k: jax.device_put(v, shard) for k, v in state.items()}

            if routing == "alltoall":
                def body(st, r, g, s, c):
                    # shared local merge, as the production step does
                    d = routed_dedup(r, capacity)
                    vals, _ = routed_cache_pull(st, r, "ps", dedup=d)
                    new, ov = routed_cache_push(st, r, g, s, c, cfg, "ps",
                                                dedup=d)
                    return new, jnp.sum(vals), ov
            else:
                def body(st, r, g, s, c):
                    vals = sharded_cache_pull(st, r, "ps")
                    new = sharded_cache_push(st, r, g, s, c, cfg, "ps")
                    return new, jnp.sum(vals), jnp.int32(0)

            fn = jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(P("ps"),) + (P("ps"),) * 4,
                out_specs=(P("ps"), P(), P()), check_vma=False),
                donate_argnums=(0,))

            rows = jnp.asarray(rng.integers(0, capacity, m_global), jnp.int32)
            grads = jnp.asarray(rng.normal(size=(m_global, 1 + dim)).astype(np.float32))
            shows = jnp.ones((m_global,), jnp.float32)
            clicks = jnp.asarray((rng.random(m_global) < 0.4).astype(np.float32))

            ss, val, ov = fn(ss, rows, grads, shows, clicks)  # compile
            jax.block_until_ready(val)
            assert int(ov) == 0
            t0 = time.perf_counter()
            for _ in range(steps):
                ss, val, ov = fn(ss, rows, grads, shows, clicks)
            jax.block_until_ready(val)
            dt = (time.perf_counter() - t0) / steps
            res[str(K)] = round(dt * 1e3, 3)  # ms/step
        out["modes"][routing] = res

    # scaling ratio: gathered cost grows with K, routed stays ~flat —
    # the K=max vs K=min cost ratio per mode
    lo, hi = str(min(shard_counts)), str(max(shard_counts))
    out["growth"] = {
        m: round(out["modes"][m][hi] / out["modes"][m][lo], 2)
        for m in out["modes"]
    }
    path = os.environ.get("RS_OUT") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "ROUTED_SCALING.json" if push_mode == "sparse"
        else "ROUTED_SCALING_DENSE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()

"""Measure the full routed×dense composition grid (VERDICT r3 #2):
per-step wall time of the sharded cache serving under every
(pull_routing, push_routing) × push_mode combination, across a
(batch, capacity, K) grid on the virtual CPU mesh — the calibration
evidence behind ``paddle_tpu.ps.sharded_cache.select_routing``.

Eight combos per cell: pull ∈ {alltoall, allgather} × push ∈ {alltoall,
allgather} × push_mode ∈ {dense, sparse}. For each cell the artifact
records the ms/step of every combo, the combo ``select_routing`` picks,
and whether that pick is ever the WORST of its push_mode's four — the
acceptance gate is that it never is.

CPU devices share one host, so absolute numbers are not TPU numbers,
but the per-shard WORK ratios the decision rule keys on show directly.
Re-run on hardware with RG_PLATFORM=tpu when the chip allows (the
default is cpu; note a single chip can only measure K=1 — the
multi-chip grid needs a pod).

Writes ROUTED_GRID.json. Env: RG_BATCHES ("128,1024"), RG_SLOTS (26),
RG_DIM (8), RG_STEPS (10), RG_SHARDS ("2,8"), RG_CAPS ("65536,1048576").
"""

import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

ABBR = {"alltoall": "a2a", "allgather": "ag"}


def main() -> None:
    import jax

    platform = os.environ.get("RG_PLATFORM", "cpu")
    jax.config.update("jax_platforms", platform)
    if platform == "cpu":  # before any backend-initializing jax call
        jax.config.update("jax_num_cpu_devices", 8)
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ps.embedding_cache import CacheConfig
    from paddle_tpu.ps.sharded_cache import (routed_cache_pull,
                                             routed_cache_push,
                                             routed_dedup, select_routing,
                                             sharded_cache_pull,
                                             sharded_cache_push)

    batches = [int(b) for b in
               os.environ.get("RG_BATCHES", "128,1024").split(",")]
    S = int(os.environ.get("RG_SLOTS", 26))
    dim = int(os.environ.get("RG_DIM", 8))
    steps = int(os.environ.get("RG_STEPS", 10))
    shard_counts = [int(k) for k in
                    os.environ.get("RG_SHARDS", "2,8").split(",")]
    caps = [int(c) for c in
            os.environ.get("RG_CAPS", "65536,1048576").split(",")]
    rng = np.random.default_rng(0)
    devices = jax.devices()

    def fresh(cap, key):
        r = np.random.default_rng(key)
        return {
            "show": jnp.asarray(r.uniform(0, 5, cap).astype(np.float32)),
            "click": jnp.asarray(r.uniform(0, 2, cap).astype(np.float32)),
            "embed_w": jnp.asarray(r.normal(size=(cap, 1)).astype(np.float32)),
            "embed_state": jnp.asarray(r.uniform(0, 1, (cap, 1)).astype(np.float32)),
            "embedx_w": jnp.asarray(r.normal(size=(cap, dim)).astype(np.float32)),
            "embedx_state": jnp.asarray(r.uniform(0, 1, (cap, 1)).astype(np.float32)),
            "has_embedx": jnp.asarray((r.random(cap) < 0.5).astype(np.float32)),
        }

    def make_body(pull_r, push_r, cfg, capacity):
        def body(st, r, g, s, c):
            d = None
            if "alltoall" in (pull_r, push_r):
                d = routed_dedup(r, capacity)
            if pull_r == "alltoall":
                vals, _ = routed_cache_pull(st, r, "ps", dedup=d)
            else:
                vals = sharded_cache_pull(st, r, "ps")
            if push_r == "alltoall":
                new, ov = routed_cache_push(st, r, g, s, c, cfg, "ps",
                                            dedup=d)
            else:
                new = sharded_cache_push(st, r, g, s, c, cfg, "ps")
                ov = jnp.int32(0)
            return new, jnp.sum(vals), ov
        return body

    cells = []
    never_worst = True
    for B, capacity, K in itertools.product(batches, caps, shard_counts):
        assert len(devices) >= K, (
            f"RG_SHARDS asks for {K} shards but only {len(devices)} "
            "devices exist — the cell would be silently mislabeled")
        mesh = Mesh(np.array(devices[:K]), ("ps",))
        shard = NamedSharding(mesh, P("ps"))
        m_global = B * S
        rows = jnp.asarray(rng.integers(0, capacity, m_global), jnp.int32)
        grads = jnp.asarray(
            rng.normal(size=(m_global, 1 + dim)).astype(np.float32))
        shows = jnp.ones((m_global,), jnp.float32)
        clicks = jnp.asarray((rng.random(m_global) < 0.4).astype(np.float32))
        cell = {"batch": B, "capacity": capacity, "K": K, "ms": {}}
        for push_mode in ("dense", "sparse"):
            cfg = CacheConfig(capacity=capacity, embedx_dim=dim,
                              embedx_threshold=0.0, push_mode=push_mode)
            for pull_r, push_r in itertools.product(
                    ("alltoall", "allgather"), repeat=2):
                ss = {k: jax.device_put(v, shard)
                      for k, v in fresh(capacity, 0).items()}
                fn = jax.jit(shard_map(
                    make_body(pull_r, push_r, cfg, capacity), mesh=mesh,
                    in_specs=(P("ps"),) + (P("ps"),) * 4,
                    out_specs=(P("ps"), P(), P()), check_vma=False),
                    donate_argnums=(0,))
                ss, val, ov = fn(ss, rows, grads, shows, clicks)  # compile
                jax.block_until_ready(val)
                assert int(ov) == 0
                # min-of-3: CPU-mesh run-to-run variance at the 15-20 ms
                # scale exceeds combo spreads; min is the standard
                # variance-killing estimator for a deterministic program
                dt = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(steps):
                        ss, val, ov = fn(ss, rows, grads, shows, clicks)
                    jax.block_until_ready(val)
                    dt = min(dt, (time.perf_counter() - t0) / steps)
                cell["ms"][f"{push_mode}:{ABBR[pull_r]}-pull/"
                           f"{ABBR[push_r]}-push"] = round(dt * 1e3, 3)
            sel = select_routing(m_global // K, capacity // K, K, push_mode)
            key = (f"{push_mode}:{ABBR[sel[0]]}-pull/{ABBR[sel[1]]}-push")
            four = {k: v for k, v in cell["ms"].items()
                    if k.startswith(push_mode + ":")}
            worst = max(four, key=four.get)
            spread = four[worst] / min(four.values())
            # a cell whose best-to-worst spread is under 10% is a TIE —
            # e.g. dense push with C/K >> batch, where the O(C/K)
            # full-table update dominates every combo equally; "worst"
            # is not meaningful there and the spread is recorded so the
            # call is auditable
            is_worst = key == worst and spread > 1.10
            cell[f"selected_{push_mode}"] = key
            cell[f"spread_{push_mode}"] = round(spread, 3)
            cell[f"selected_is_worst_{push_mode}"] = is_worst
            never_worst &= not is_worst
        cells.append(cell)
        print(json.dumps(cell), flush=True)

    out = {
        "slots": S, "dim": dim, "steps": steps,
        "platform": jax.default_backend(),
        "cells": cells,
        "auto_never_worst": never_worst,
    }
    path = os.environ.get("RG_OUT") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "ROUTED_GRID.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"auto_never_worst": never_worst, "cells": len(cells)}))


if __name__ == "__main__":
    main()

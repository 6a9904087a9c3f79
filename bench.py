"""DeepFM/Criteo-shaped train-step throughput on one TPU chip.

One configuration, one JSON line, and a non-zero exit on any failure —
including finding no TPU: a rate from another backend is not this
metric. (The round's benchmark — cells, regression bounds, a traced
per-layer breakdown — is ROADMAP S1's; until it lands this is the one
cell, kept honest.)

What runs: the GPUPS-style pass step — ONE jitted XLA program per
dispatch doing, ``SLAB`` times over a device-resident stack of packed
wire buffers, the in-graph feasign→row probe, the embedding pull,
DeepFM forward/backward in bf16 contractions, the dense Adam update and
the per-feature CTR AdaGrad push on the HBM-resident cache. Criteo
shape: 26 sparse slots, 13 dense features, embedx_dim 8, DNN
400×400×400, batch 4096, a 2^21-row cache holding a ~2^20-key pass.
Every ``auto`` switch is left at ``auto``; the line says what the push
mode resolved to.
"""

import json
import time

import numpy as np

METRIC = "deepfm_criteo_samples_per_sec_per_chip"
BATCH = 4096
SLAB = 8           # train steps per dispatch
WARMUP = 5         # dispatches before the clock starts (the first compiles)
STEPS = 30         # timed dispatches
PASS_KEYS = 1 << 20
N_BATCHES = 8      # distinct pre-generated host slabs, cycled


def main() -> None:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.data.prefetcher import device_prefetch
    from paddle_tpu.models.ctr import (CtrConfig, DeepFM, make_random_packs,
                                       make_ctr_train_step_slab)
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.embedding_cache import (CacheConfig, HbmEmbeddingCache,
                                               resolve_push_mode)
    from paddle_tpu.ps.table import MemorySparseTable, TableConfig

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures a TPU chip; jax found platform="
            f"{dev.platform!r} ({dev.device_kind})")

    cfg = CtrConfig(num_sparse_slots=26, num_dense=13, embedx_dim=8,
                    dnn_hidden=(400, 400, 400))
    cache_cfg = CacheConfig(capacity=1 << 21, embedx_dim=cfg.embedx_dim,
                            embedx_threshold=0.0)
    pt.seed(0)
    rng = np.random.default_rng(0)
    table = MemorySparseTable(TableConfig(
        shard_num=16, accessor_config=AccessorConfig(embedx_dim=cfg.embedx_dim)))
    cache = HbmEmbeddingCache(table, cache_cfg, device_map=True)
    # pass working set: PASS_KEYS slot-tagged feasigns, drawn uniformly
    pool = rng.integers(0, PASS_KEYS // 26 + 1,
                        size=(PASS_KEYS, 26)).astype(np.uint64)
    pool += np.arange(26, dtype=np.uint64) << np.uint64(32)
    cache.begin_pass(pool.reshape(-1))

    model = DeepFM(cfg)
    opt = optimizer.Adam(learning_rate=1e-3)
    step = make_ctr_train_step_slab(
        model, opt, cache_cfg, slot_ids=np.arange(26), batch_size=BATCH,
        num_dense=cfg.num_dense, slab=SLAB, amp=True)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    opt_state = opt.init(params)
    map_state = cache.device_map.state
    cache_state = cache.state

    # host batches are generated up front (the input pipeline is not what
    # this cell measures) and fed through the async H2D prefetcher
    slabs = [np.stack(make_random_packs(rng, pool, BATCH, cfg.num_dense, SLAB))
             for _ in range(N_BATCHES)]
    prefetcher = device_prefetch(
        (slabs[i % N_BATCHES] for i in range(WARMUP + STEPS)), depth=3)
    feeder = iter(prefetcher)
    try:
        for _ in range(WARMUP):
            params, opt_state, cache_state, losses = step(
                params, opt_state, cache_state, map_state, next(feeder))
        jax.block_until_ready(losses)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            params, opt_state, cache_state, losses = step(
                params, opt_state, cache_state, map_state, next(feeder))
        jax.block_until_ready(losses)
        dt = time.perf_counter() - t0
    finally:
        prefetcher.close()
    if not bool(jnp.isfinite(losses).all()):
        raise RuntimeError(f"non-finite loss in the timed window: {losses}")

    print(json.dumps({
        "metric": METRIC, "value": round(BATCH * SLAB * STEPS / dt, 1),
        "unit": "samples/s", "platform": dev.platform,
        "device_kind": dev.device_kind, "device_count": len(jax.devices()),
        "batch": BATCH, "slab": SLAB, "steps": STEPS, "amp": True,
        "push_mode": resolve_push_mode(
            cache_cfg.push_mode, cache_cfg.capacity,
            BATCH * cfg.num_sparse_slots)}))


if __name__ == "__main__":
    main()

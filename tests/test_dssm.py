"""DSSM two-tower recall (models/dssm.py): in-batch-negatives training
through the GPUPS pass path learns a query↔doc pairing structure, and
retrieval ranks the true doc above batch negatives."""

import jax.numpy as jnp
import numpy as np

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.ps.embedding_cache import cache_pull
from paddle_tpu.models.dssm import DSSM, make_dssm_train_step
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.embedding_cache import CacheConfig, HbmEmbeddingCache
from paddle_tpu.ps.table import MemorySparseTable, TableConfig

SQ, SD, DIM = 2, 2, 8
N_PAIRS = 48  # latent topics: query topic t pairs with doc topic t


def _synth(rng, n):
    """Query slots drawn from topic-t query vocab; the paired doc's
    slots from topic-t doc vocab — towers must embed both sides of a
    topic near each other."""
    topic = rng.integers(0, N_PAIRS, size=n).astype(np.uint64)
    q = (topic[:, None] * np.uint64(4)
         + rng.integers(0, 4, size=(n, SQ)).astype(np.uint64) + np.uint64(1))
    d = (topic[:, None] * np.uint64(4)
         + rng.integers(0, 4, size=(n, SD)).astype(np.uint64) + np.uint64(1)
         + (np.uint64(1) << np.uint64(32)))  # doc slot-space tag
    keys = np.concatenate([q, d], axis=1)
    dense = np.zeros((n, 1), np.float32)
    labels = np.ones(n, np.int32)
    return keys, dense, labels


def test_dssm_learns_pairing_and_ranks_true_doc():
    pt.seed(0)
    rng = np.random.default_rng(0)
    cache_cfg = CacheConfig(capacity=2048, embedx_dim=DIM,
                            embedx_threshold=0.0)
    # embedx_threshold=0 on the TABLE accessor too: DSSM's objective is
    # purely bilinear in the embx vectors — lazily-created all-zero embx
    # would put both towers at an exact saddle (zero gradients)
    table = MemorySparseTable(TableConfig(
        shard_num=4, accessor_config=AccessorConfig(
            embedx_dim=DIM, embedx_threshold=0.0)))
    cache = HbmEmbeddingCache(table, cache_cfg)

    keys, dense, labels = _synth(rng, 2048)
    cache.begin_pass(keys.reshape(-1))
    model = DSSM(SQ, SD, DIM)
    opt = optimizer.Adam(learning_rate=3e-3)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    opt_state = opt.init(params)
    step = make_dssm_train_step(model, opt, cache_cfg,
                                temperature=0.2, donate=False)

    B = 128
    losses = []
    for epoch in range(40):
        for i in range(0, len(keys), B):
            rows = jnp.asarray(
                cache.lookup(keys[i:i + B].reshape(-1)).reshape(B, SQ + SD))
            params, opt_state, cache.state, loss = step(
                params, opt_state, cache.state, rows,
                jnp.asarray(dense[i:i + B]), jnp.asarray(labels[i:i + B]))
            losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

    # retrieval check: within held-out batches, the true doc must rank
    # top-1 among the in-batch candidates far above the 1/B chance rate
    keys2, dense2, _ = _synth(rng, 512)
    hits = total = 0
    for i in range(0, len(keys2), B):
        k = keys2[i:i + B]
        rows = jnp.asarray(cache.lookup(k.reshape(-1)).reshape(B, SQ + SD))
        emb = cache_pull(cache.state, rows.reshape(-1)).reshape(
            B, SQ + SD, -1)
        (q, d), _ = nn.functional_call(model, params, emb,
                                       jnp.asarray(dense2[i:i + B]),
                                       training=False)
        sim = np.asarray(q @ d.T)
        hits += int((sim.argmax(axis=1) == np.arange(B)).sum())
        total += B
    top1 = hits / total
    assert top1 > 0.25, top1  # chance = 1/128 ≈ 0.008


def test_padded_examples_are_not_fake_negatives():
    """The padding contract: a tail batch's padded rows must not act as
    in-batch negatives — real rows' losses are identical whether the
    batch carries padding or not."""
    import jax

    pt.seed(0)
    rng = np.random.default_rng(3)
    model = DSSM(SQ, SD, DIM)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    B, Breal = 8, 5
    emb = jnp.asarray(rng.normal(scale=0.1, size=(B, SQ + SD, 1 + DIM)),
                      jnp.float32)
    dense = jnp.zeros((B, 1), jnp.float32)
    w = jnp.asarray((np.arange(B) < Breal).astype(np.float32))
    out_full, _ = nn.functional_call(model, params, emb, dense,
                                     training=False)
    per_masked = DSSM.loss_vec(out_full, None, 0.2, weights=w)
    out_real, _ = nn.functional_call(model, params, emb[:Breal], dense[:Breal],
                                     training=False)
    per_real = DSSM.loss_vec(out_real, None, 0.2)
    np.testing.assert_allclose(np.asarray(per_masked)[:Breal],
                               np.asarray(per_real), rtol=1e-5)
    assert np.isfinite(np.asarray(per_masked)).all()


def test_dssm_tower_export(tmp_path):
    """export_dssm_towers: query and doc towers export as separate
    portable programs (ANN-index build + online query, the module's
    promised serving split); loaded towers reproduce the in-process
    normalized vectors and their dot ranks the true pairing."""
    import jax

    from paddle_tpu.io.inference import load_inference_model
    from paddle_tpu.models.dssm import export_dssm_towers
    from paddle_tpu.nn.layer import functional_call

    pt.seed(0)
    rng = np.random.default_rng(0)
    cache_cfg = CacheConfig(capacity=2048, embedx_dim=DIM,
                            embedx_threshold=0.0)
    table = MemorySparseTable(TableConfig(
        shard_num=4, accessor_config=AccessorConfig(
            embedx_dim=DIM, embedx_threshold=0.0)))
    cache = HbmEmbeddingCache(table, cache_cfg, device_map=True)
    keys, dense, labels = _synth(rng, 256)
    cache.begin_pass(keys.reshape(-1))
    # non-trivial table values so towers output distinct vectors
    cache.state["embedx_w"] = jnp.asarray(
        rng.normal(size=cache.state["embedx_w"].shape).astype(np.float32))

    model = DSSM(SQ, SD, DIM)
    # _synth's key scheme: every query slot lives in hi=0 key space,
    # every doc slot in hi=1 (the doc slot-space tag)
    export_dssm_towers(str(tmp_path), model, cache,
                       query_slot_ids=np.zeros(SQ, np.uint32),
                       doc_slot_ids=np.ones(SD, np.uint32))
    q_pred = load_inference_model(str(tmp_path / "query"))
    d_pred = load_inference_model(str(tmp_path / "doc"))

    B = 16
    lo = (keys[:B] & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    q_vec = np.asarray(q_pred(jnp.asarray(lo[:, :SQ])))
    d_vec = np.asarray(d_pred(jnp.asarray(lo[:, SQ:])))
    assert q_vec.shape == d_vec.shape == (B, 16)
    np.testing.assert_allclose(np.linalg.norm(q_vec, axis=1), 1.0,
                               atol=1e-3)

    # in-process reference through the full model
    rows = jnp.asarray(cache.lookup(keys[:B].reshape(-1)).reshape(
        B, SQ + SD))
    emb = cache_pull(cache.state, rows.reshape(-1)).reshape(B, SQ + SD, -1)
    (q_ref, d_ref), _ = functional_call(
        model, {"params": dict(model.named_parameters()), "buffers": {}},
        emb, jnp.asarray(dense[:B]), training=False)
    np.testing.assert_allclose(q_vec, np.asarray(q_ref), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(d_vec, np.asarray(d_ref), rtol=1e-5,
                               atol=1e-5)

    # params-only refresh (the online query-tower update): mutate the
    # tables, overwrite values — the programs are untouched and a fresh
    # predictor serves moved vectors
    import os

    prog = tmp_path / "query" / "model.stablehlo"
    before = prog.read_bytes()
    cache.state["embed_w"] = cache.state["embed_w"] * 2.0
    export_dssm_towers(str(tmp_path), model, cache,
                       query_slot_ids=np.zeros(SQ, np.uint32),
                       doc_slot_ids=np.ones(SD, np.uint32),
                       refresh_only=True)
    assert prog.read_bytes() == before
    q2 = np.asarray(load_inference_model(str(tmp_path / "query"))(
        jnp.asarray(lo[:, :SQ])))
    assert not np.allclose(q2, q_vec)
    np.testing.assert_allclose(np.linalg.norm(q2, axis=1), 1.0, atol=1e-3)

"""DIN (models/din.py): attention over variable-length behavior slots
through the GPUPS pass path — learns a behavior-match signal sum-pooling
can't express cleanly, and provably ignores padding positions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.metrics.auc import AUC
from paddle_tpu.ps.embedding_cache import cache_pull
from paddle_tpu.models.din import DIN, make_ctr_attention_train_step
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.embedding_cache import CacheConfig, HbmEmbeddingCache
from paddle_tpu.ps.table import MemorySparseTable, TableConfig

G, TB, D, DIM = 1, 6, 2, 8  # target cols, behavior cols, dense, emb dim
VOCAB = 64


def _synth(rng, n):
    """Target item + a variable-length behavior history; the label
    depends on how many REAL history items are 'clicky' (id%5==0) —
    learnable per-item structure that must flow through the attention
    pooling, where only the mask keeps padding out of the count. (Pure
    target∈history identity matching is deliberately NOT the gate: at
    test scale that is a research-grade embedding-identity problem, not
    a framework property.)"""
    target = rng.integers(1, VOCAB, size=(n, G)).astype(np.uint64)
    lens = rng.integers(1, TB + 1, size=n)
    behav = rng.integers(1, VOCAB, size=(n, TB)).astype(np.uint64)
    # target and behaviors SHARE the item embedding space (DIN's
    # shared item embedding) — same feasign for the same item
    keys = np.concatenate([target, behav], axis=1)
    pad_mask = np.arange(TB)[None, :] < lens[:, None]
    clicky = ((behav % np.uint64(5) == 0) & pad_mask).sum(axis=1)
    dense = rng.normal(size=(n, D)).astype(np.float32)
    labels = ((clicky + dense[:, 0]
               + rng.normal(scale=0.5, size=n)) > 1.3).astype(np.int32)
    return keys, pad_mask, dense, labels


def test_din_learns_match_signal_and_ignores_padding():
    pt.seed(0)
    rng = np.random.default_rng(0)
    cache_cfg = CacheConfig(capacity=1024, embedx_dim=DIM,
                            embedx_threshold=0.0)
    table = MemorySparseTable(TableConfig(
        shard_num=4, accessor_config=AccessorConfig(embedx_dim=DIM)))
    cache = HbmEmbeddingCache(table, cache_cfg)

    keys, pad_mask, dense, labels = _synth(rng, 2048)
    cache.begin_pass(keys.reshape(-1))
    C = cache_cfg.capacity

    def rows_of(k, mask):
        r = cache.lookup(k.reshape(-1)).reshape(k.shape).astype(np.int32)
        full = np.concatenate(
            [np.ones((len(k), G), bool), mask], axis=1)
        return np.where(full, r, C)  # padding → sentinel

    model = DIN(G, TB, D, DIM)
    opt = optimizer.Adam(learning_rate=1e-2)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    opt_state = opt.init(params)
    step = make_ctr_attention_train_step(model, opt, cache_cfg,
                                         donate=False)

    B = 256
    for epoch in range(12):
        for i in range(0, len(keys), B):
            rows = jnp.asarray(rows_of(keys[i:i + B], pad_mask[i:i + B]))
            params, opt_state, cache.state, loss = step(
                params, opt_state, cache.state, rows,
                jnp.asarray(dense[i:i + B]), jnp.asarray(labels[i:i + B]))
    assert np.isfinite(float(loss))

    m = AUC()
    for i in range(0, len(keys), B):
        rows = jnp.asarray(rows_of(keys[i:i + B], pad_mask[i:i + B]))
        # cache_pull is sentinel-safe: rows >= capacity pull zeros
        emb = cache_pull(cache.state, rows.reshape(-1)).reshape(
            rows.shape[0], G + TB, -1)
        real = (rows < C).astype(jnp.float32)
        out, _ = nn.functional_call(model, params, emb, real,
                                    jnp.asarray(dense[i:i + B]),
                                    training=False)
        m.update(np.asarray(nn.functional.sigmoid(out)), labels[i:i + B])
    auc = m.accumulate()
    assert auc > 0.8, auc

    # padding invariance: corrupt the PADDED positions' embeddings with
    # garbage — outputs must not change (the mask, not zero-embeddings,
    # is what excludes padding)
    i = 0
    rows = jnp.asarray(rows_of(keys[i:i + B], pad_mask[i:i + B]))
    emb = np.array(cache_pull(cache.state, rows.reshape(-1)).reshape(
        B, G + TB, -1))
    real = np.asarray(rows) < C
    out1, _ = nn.functional_call(model, params, jnp.asarray(emb),
                                 jnp.asarray(real.astype(np.float32)),
                                 jnp.asarray(dense[:B]), training=False)
    emb2 = emb.copy()
    emb2[~real] = 777.0  # garbage in every padded position
    out2, _ = nn.functional_call(model, params, jnp.asarray(emb2),
                                 jnp.asarray(real.astype(np.float32)),
                                 jnp.asarray(dense[:B]), training=False)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    cache.end_pass()
    assert table.size() >= len(np.unique(keys))

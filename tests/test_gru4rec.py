"""GRU4Rec session recall (models/gru4rec.py) + the nn.GRU/LSTM layers
it rides on. Synthetic signal: sessions walk within an item cluster
and the next item comes from the same cluster — after training the
session vector must rank the true next item above in-batch negatives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.models.gru4rec import (GRU4Rec, item_keys,
                                       make_gru4rec_train_step)
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.embedding_cache import CacheConfig, HbmEmbeddingCache
from paddle_tpu.ps.sgd_rule import SGDRuleConfig
from paddle_tpu.ps.table import MemorySparseTable, TableConfig

N_ITEMS, N_CLUSTERS, T = 32, 4, 5


def _sessions(rng, n):
    cluster = rng.integers(0, N_CLUSTERS, n)
    lo = cluster * (N_ITEMS // N_CLUSTERS)
    span = N_ITEMS // N_CLUSTERS
    seq = lo[:, None] + rng.integers(0, span, (n, T))
    lengths = rng.integers(2, T + 1, n)
    target = lo + rng.integers(0, span, n)
    return seq.astype(np.uint64), lengths, target.astype(np.uint64), cluster


def test_gru_masking_and_shapes(rng):
    pt.seed(0)
    gru = nn.GRU(4, 8, num_layers=2)
    x = jnp.asarray(rng.normal(size=(3, 6, 4)).astype(np.float32))
    lengths = jnp.asarray([6, 2, 4])
    out, h = gru(x, lengths)
    assert out.shape == (3, 6, 8) and h.shape == (2, 3, 8)
    o = np.asarray(out)
    assert (o[1, 2:] == 0).all() and (o[2, 4:] == 0).all()
    # final state = last REAL step's output
    np.testing.assert_allclose(np.asarray(h)[1][1], o[1, 1], rtol=1e-6)

    lstm = nn.LSTM(4, 8)
    o2, (h2, c2) = lstm(x, lengths)
    assert o2.shape == (3, 6, 8) and h2.shape == c2.shape == (1, 3, 8)
    assert (np.asarray(o2)[1, 2:] == 0).all()


def test_gru4rec_learns_session_recall(rng):
    pt.seed(0)
    dim = 8
    sgd = SGDRuleConfig(learning_rate=0.1)
    acc = AccessorConfig(embedx_dim=dim, embedx_threshold=0.0, sgd=sgd)
    table = MemorySparseTable(TableConfig(shard_num=2,
                                          accessor_config=acc))
    cache_cfg = CacheConfig(capacity=1 << 8, embedx_dim=dim,
                            embedx_threshold=0.0, sgd=sgd)
    cache = HbmEmbeddingCache(table, cache_cfg, device_map=True)
    cache.begin_pass(item_keys(np.arange(N_ITEMS)))
    cache.state["embedx_w"] = jnp.asarray(
        rng.normal(scale=0.1,
                   size=cache.state["embedx_w"].shape).astype(np.float32))

    model = GRU4Rec(embedx_dim=dim, hidden=16, out_dim=8)
    opt = optimizer.Adam(5e-3)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    opt_state = opt.init(params)
    step = make_gru4rec_train_step(model, opt, cache_cfg, donate=False)

    C = cache_cfg.capacity
    losses = []
    for it in range(120):
        seq, lengths, target, _ = _sessions(rng, 32)
        rows_seq = cache.lookup(item_keys(seq.reshape(-1))).reshape(
            seq.shape).astype(np.int32)
        # positions past length use the sentinel (padding contract)
        pad = np.arange(T)[None, :] >= lengths[:, None]
        rows_seq = np.where(pad, C, rows_seq)
        rows_tgt = cache.lookup(item_keys(target)).astype(np.int32)
        params, opt_state, cache.state, loss = step(
            params, opt_state, cache.state, jnp.asarray(rows_seq),
            jnp.asarray(rows_tgt), jnp.asarray(lengths))
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) * 0.7, (
        np.mean(losses[:10]), np.mean(losses[-10:]))

    # retrieval: the true next item ranks above most in-batch negatives
    seq, lengths, target, cluster = _sessions(rng, 64)
    rows_seq = cache.lookup(item_keys(seq.reshape(-1))).reshape(
        seq.shape).astype(np.int32)
    pad = np.arange(T)[None, :] >= lengths[:, None]
    rows_seq = np.where(pad, C, rows_seq)
    rows_tgt = cache.lookup(item_keys(target)).astype(np.int32)
    from paddle_tpu.ps.embedding_cache import cache_pull

    emb_seq = cache_pull(cache.state, jnp.asarray(rows_seq.reshape(-1))
                         ).reshape(64, T, -1)
    emb_tgt = cache_pull(cache.state, jnp.asarray(rows_tgt))
    (u, v), _ = nn.functional_call(model, params, emb_seq, emb_tgt,
                                   jnp.asarray(lengths), training=False)
    scores = np.asarray(u @ v.T)                 # [B, B]
    # in-batch negatives include ~B/N_CLUSTERS same-cluster items that
    # are equally valid nexts, capping rank-of-target metrics — the
    # learnable signal is the CLUSTER: same-cluster targets must score
    # above cross-cluster ones (AUC over the score matrix)
    same = cluster[:, None] == cluster[None, :]
    pos, neg = scores[same], scores[~same]
    auc = float(np.mean(pos[:, None] > neg[None, :]))
    assert auc > 0.85, auc                        # random = 0.5
    # and the true target still beats clear majority of CROSS-cluster
    # negatives per example
    ranks_cross = ((scores > np.diag(scores)[:, None]) & ~same).sum(1)
    assert float(np.mean(ranks_cross)) < 3.0, ranks_cross.mean()


def test_gru4rec_tower_exports(rng, tmp_path):
    """export_gru4rec_towers: the session tower (keys+lengths →
    normalized session vector, GRU scan inside a batch-polymorphic
    portable program) and the item tower (keys → normalized vectors)
    match the in-process forward; padding past lengths and out-of-pass
    ids hit the sentinel; refresh_only swaps values without touching
    the programs."""
    from paddle_tpu.io.inference import load_inference_model
    from paddle_tpu.models.gru4rec import export_gru4rec_towers
    from paddle_tpu.ps.embedding_cache import cache_pull

    pt.seed(0)
    dim = 8
    acc = AccessorConfig(embedx_dim=dim, embedx_threshold=0.0,
                         sgd=SGDRuleConfig(initial_range=0.0))
    table = MemorySparseTable(TableConfig(shard_num=2, accessor_config=acc))
    cache_cfg = CacheConfig(capacity=1 << 8, embedx_dim=dim,
                            embedx_threshold=0.0)
    cache = HbmEmbeddingCache(table, cache_cfg, device_map=True)
    cache.begin_pass(item_keys(np.arange(N_ITEMS)))
    # an item's embedding is the item's, wherever the pass put its row
    # (large enough that no tower output is small beside the smoothed
    # normalisation's eps: the norms below are 1 to 1e-3)
    at = cache.lookup(item_keys(np.arange(N_ITEMS)))
    for col in ("embedx_w", "embed_w"):
        vals = rng.normal(scale=0.3, size=cache.state[col].shape)
        cache.state[col] = jnp.zeros_like(cache.state[col]).at[at].set(
            vals[:N_ITEMS].astype(np.float32))

    model = GRU4Rec(embedx_dim=dim, hidden=16, out_dim=8)
    export_gru4rec_towers(str(tmp_path), model, cache, max_len=T)
    sess = load_inference_model(str(tmp_path / "session"))
    item = load_inference_model(str(tmp_path / "item"))

    seq, lengths, target, _ = _sessions(rng, 8)
    C = cache_cfg.capacity
    # serving feeds RAW lo32 ids; pad positions use an out-of-pass id
    lo = seq.astype(np.uint32)
    pad = np.arange(T)[None, :] >= lengths[:, None]
    lo = np.where(pad, np.uint32(0xFFFFFF), lo)
    u = np.asarray(sess(jnp.asarray(lo), jnp.asarray(lengths, jnp.int32)))
    v = np.asarray(item(jnp.asarray(target[:, None].astype(np.uint32))))
    assert u.shape == (8, 8) and v.shape == (8, 8)
    np.testing.assert_allclose(np.linalg.norm(u, axis=1), 1.0, atol=1e-3)
    np.testing.assert_allclose(np.linalg.norm(v, axis=1), 1.0, atol=1e-3)

    # in-process oracle through the training forward
    rows_seq = cache.lookup(item_keys(seq.reshape(-1))).reshape(
        seq.shape).astype(np.int32)
    rows_seq = np.where(pad, C, rows_seq)
    rows_tgt = cache.lookup(item_keys(target)).astype(np.int32)
    emb_seq = cache_pull(cache.state, jnp.asarray(rows_seq.reshape(-1))
                         ).reshape(8, T, -1)
    emb_tgt = cache_pull(cache.state, jnp.asarray(rows_tgt))
    (u_ref, v_ref), _ = nn.functional_call(
        model, {"params": dict(model.named_parameters()), "buffers": {}},
        emb_seq, emb_tgt, jnp.asarray(lengths), training=False)
    np.testing.assert_allclose(u, np.asarray(u_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v, np.asarray(v_ref), rtol=1e-5, atol=1e-5)

    # refresh_only: tables move, programs byte-identical, vectors move
    prog = tmp_path / "session" / "model.stablehlo"
    before = prog.read_bytes()
    cache.state["embedx_w"] = cache.state["embedx_w"] * 2.0
    export_gru4rec_towers(str(tmp_path), model, cache, max_len=T,
                          refresh_only=True)
    assert prog.read_bytes() == before
    u2 = np.asarray(load_inference_model(str(tmp_path / "session"))(
        jnp.asarray(lo), jnp.asarray(lengths, jnp.int32)))
    assert not np.allclose(u2, u)

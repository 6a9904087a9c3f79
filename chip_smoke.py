"""The quickest proof that the system still starts on the chip.

``python3 chip_smoke.py`` drives the parameter-server training path once,
through the entry points a user calls, at the full widths of the models
the repo supports, on a TPU: ONE process, every failure propagates, the
exit code is non-zero on any. With every ``auto`` switch left at ``auto``:

- **pass**   — ``InMemoryDataset.load_from_lines`` → ``CtrPassTrainer(slab=8,
  amp=True).train_from_dataset`` twice over a DeepFM/Criteo-shaped pass
  (26 slots, 13 dense, embedx_dim 8, tower 400×400×400, batch 4096, a
  2^21-row HBM cache holding ~2^20 pass keys), then one f32 step on the
  device against the same step on the host CPU.
- **stream** — two loopback ``NativePsServer``s, ``SyncCommunicator(
  RpcPsClient)``, ``CtrStreamTrainer(hot_tier=HotTierConfig(capacity=1<<21))``:
  a cold epoch that admits the working set, a warm one that must perform
  zero PS RPCs, a flush whose rows must equal the tier's.
- **dense**  — ERNIE-1.0 base (vocab 18000, hidden 768, 12 heads, ffn 3072,
  12 layers, seq 512, batch 16) through ``Trainer(amp=True)``, three
  steps; the Pallas flash kernel must be compiled (not interpreted) and
  agree with ``local_attention`` at that head shape, once more under
  a sliding window at 28 query / 4 key-value heads of 128, and under
  EvaByte's stated mask (aligned windows beside chunk summaries) at 32
  heads of 128.
- **four**   — with ≥ 4 devices: the key-routed sharded CTR step on
  ``{"ps": 4}`` at the same widths against the one-device step, then the
  hybrid ERNIE step of ``__graft_entry__`` on the four real devices.

Before JAX is touched the native library is rebuilt from
``paddle_tpu/csrc`` (never a library some other machine built). The
persistent compilation cache is placed by ``enable_compile_cache``; the
run says how many programs came out of it and how many it wrote.

The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Weights and data are random, from fixed seeds; nothing outside the
checkout is read or written and no network is used.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from typing import Dict, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Widths of one run. The defaults ARE the smoke; tests/test_chip_smoke.py
    shrinks them to drive the same legs on CPU."""

    # DeepFM / Criteo (bench.py's widths)
    slots: int = 26
    dense: int = 13
    embedx_dim: int = 8
    tower: Tuple[int, ...] = (400, 400, 400)
    batch: int = 4096
    capacity: int = 1 << 21
    ids_per_slot: int = (1 << 20) // 26   # ~2^20 distinct keys a pass
    pass_batches: int = 32
    slab: int = 8
    stream_batches: int = 16
    # ERNIE-1.0 base
    vocab: int = 18000
    hidden: int = 768
    heads: int = 12
    ffn: int = 3072
    layers: int = 12
    seq: int = 512
    ernie_batch: int = 16
    ernie_steps: int = 3
    # one windowed flash call at SmallThinker's head shape: 28 query heads
    # on 4 key-value heads of 128, a 512-key window over 2048 positions
    # (4 x 4 blocks of 512: 7 of the 10 causal pairs walked)
    window_heads: Tuple[int, int, int] = (28, 4, 128)
    window_seq: int = 2048
    window: int = 512
    # one call under EvaByte's mask at its head shape: 32 heads of 128,
    # aligned windows of 2048 keys in chunks of 16 over 4096 positions
    # (8 x 9 blocks of 512: 2 x 10 local pairs + 4 on the summaries)
    eva_heads: Tuple[int, int] = (32, 128)
    eva_seq: int = 4096
    eva_window: int = 2048
    eva_chunk: int = 16
    # one hyper-connected sublayer at Xing4.0's shape: 4 residual streams
    # of 3584 a token over 4096 tokens, 20 Sinkhorn steps
    hc_streams: int = 4
    hc_hidden: int = 3584
    hc_seq: int = 4096


def make_ctr_dataset(sz: Sizes, n_batches: int, seed: int):
    """A seeded MultiSlot text dataset with a planted signal the tower
    can learn: the label is a noisy threshold on two dense features.
    Ids are uniform over ``ids_per_slot`` per slot, so a pass holds about
    ``slots * ids_per_slot`` distinct slot-tagged keys. Returns the
    dataset and, in record order, its [n, slots] slot-tagged keys
    (slot << 32 | id), [n, dense] features and [n] labels."""
    from paddle_tpu.data.dataset import InMemoryDataset, SlotDesc

    rng = np.random.default_rng(seed)
    n = n_batches * sz.batch
    ids = rng.integers(0, sz.ids_per_slot, size=(n, sz.slots))
    dense = rng.normal(size=(n, sz.dense)).astype(np.float32)
    score = dense[:, 0] + 0.5 * dense[:, min(1, sz.dense - 1)]
    labels = (score + 0.3 * rng.normal(size=n) > 0).astype(np.int64)
    cols = ([np.char.add("1 ", ids[:, s].astype(str)) for s in range(sz.slots)]
            + [np.char.add("1 ", np.char.mod("%.4f", dense[:, d]))
               for d in range(sz.dense)]
            + [np.char.add("1 ", labels.astype(str))])
    lines = [" ".join(row) for row in zip(*cols)]
    slots = ([SlotDesc(f"s{i}", is_float=False, max_len=1)
              for i in range(sz.slots)]
             + [SlotDesc(f"d{i}", is_float=True, max_len=1)
                for i in range(sz.dense)]
             + [SlotDesc("label", is_float=True, max_len=1)])
    ds = InMemoryDataset(slots, seed=0)
    ds.load_from_lines(lines)
    return ds, _slot_tagged(ids), dense, labels


def _slot_tagged(ids: np.ndarray) -> np.ndarray:
    """[n, slots] ids → the trainers' feasigns, slot << 32 | id."""
    return ids.astype(np.uint64) + (
        np.arange(ids.shape[1], dtype=np.uint64) << np.uint64(32))


def _lo32(keys: np.ndarray) -> np.ndarray:
    return (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def _slot_names(sz: Sizes):
    return ([f"s{i}" for i in range(sz.slots)],
            [f"d{i}" for i in range(sz.dense)])


def _host_table(sz: Sizes):
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.table import MemorySparseTable, TableConfig

    return MemorySparseTable(TableConfig(
        shard_num=16, accessor_config=AccessorConfig(embedx_dim=sz.embedx_dim)))


def _cache_cfg(sz: Sizes):
    from paddle_tpu.ps.embedding_cache import CacheConfig

    return CacheConfig(capacity=sz.capacity, embedx_dim=sz.embedx_dim,
                       embedx_threshold=0.0)


def _deepfm(sz: Sizes):
    from paddle_tpu.models.ctr import CtrConfig, DeepFM

    return DeepFM(CtrConfig(num_sparse_slots=sz.slots, num_dense=sz.dense,
                            embedx_dim=sz.embedx_dim, dnn_hidden=sz.tower))


def _push_facts(since: float) -> Dict:
    """The push formulations the program resolved since ``since`` (a
    ``time.perf_counter``), with the shapes each was resolved for:
    ``cache_push`` leaves one ``pt.push.select`` span a compile."""
    from paddle_tpu.core import profiler

    picked = []
    for span in profiler.host_spans():
        if span.name == "pt.push.select" and span.t0 >= since:
            fact = {"capacity": span.counts["capacity"],
                    "rows": span.counts["rows"],
                    "mode": "dense" if span.counts["sweep"] else "sparse"}
            if fact not in picked:
                picked.append(fact)
    return {"push_mode": "+".join(sorted({f["mode"] for f in picked})),
            "push_select": picked}


def _max_diff(a, b, relative: bool = False) -> float:
    """Largest |a - b| over two pytrees' leaves; ``relative`` divides each
    leaf's by that leaf's own max |b|."""
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), (len(la), len(lb))
    worst = 0.0
    for x, y in zip(la, lb):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        if x.size:
            d = np.max(np.abs(x - y))
            worst = max(worst, float(d / np.max(np.abs(y)) if relative else d))
    return worst


# ---------------------------------------------------------------------------
# pass
# ---------------------------------------------------------------------------


def leg_pass(sz: Sizes) -> Dict:
    import jax

    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.core.profiler import compile_counts
    from paddle_tpu.models.ctr import (make_ctr_train_step_packed,
                                       pack_ctr_batch)
    from paddle_tpu.ps.embedding_cache import HbmEmbeddingCache
    from paddle_tpu.ps.ps_trainer import CtrPassTrainer

    t_leg = time.perf_counter()
    ds, tagged, dense_x, labels = make_ctr_dataset(sz, sz.pass_batches, seed=1)
    sparse, dense = _slot_names(sz)
    pt.seed(0)
    table, cache_cfg = _host_table(sz), _cache_cfg(sz)
    trainer = CtrPassTrainer(_deepfm(sz), optimizer.Adam(learning_rate=1e-3),
                             table, cache_cfg, sparse, dense, "label",
                             slab=sz.slab, amp=True)
    r1 = trainer.train_from_dataset(ds, batch_size=sz.batch)
    before = compile_counts()["requests"]
    r2 = trainer.train_from_dataset(ds, batch_size=sz.batch)
    recompiled = compile_counts()["requests"] - before
    assert r1["steps"] == r2["steps"] == sz.pass_batches, (r1, r2)
    assert np.isfinite(r1["loss"]) and np.isfinite(r2["loss"]), (r1, r2)
    assert r2["loss"] < r1["loss"], \
        f"pass 2 did not learn: {r1['loss']} -> {r2['loss']}"
    assert recompiled == 0, f"pass 2 compiled {recompiled} program(s)"

    # after end_pass the host table holds every pass key, trained: the
    # flush-back wrote show counts the freshly created rows did not have
    keys = np.unique(tagged)
    assert table.size() >= len(keys), (table.size(), len(keys))
    rows, found = table.export_full(keys)
    assert found.all(), f"{(~found).sum()} pass keys missing after end_pass"
    assert (rows[:, 3] >= 2.0).all(), "a pass key's show count did not move"

    # one f32 step on the device against the SAME step on the host CPU.
    # Both trace under matmul precision "highest" (a TPU runs an f32 dot
    # in bf16 passes otherwise — a 1e-3 disagreement), so what is left
    # is summation order and transcendental rounding. The v5e measured
    # 2.9e-6 relative on the loss, 3e-8 on the parameters and 1.5e-11 on
    # the cache rows (chip run, PR 21); the bounds leave ~10x.
    tagged, dense_x, labels = (a[:sz.batch] for a in (tagged, dense_x, labels))
    pcache = HbmEmbeddingCache(_host_table(sz), cache_cfg, device_map=True)
    pcache.begin_pass(tagged.reshape(-1))
    pt.seed(0)
    model, opt = _deepfm(sz), optimizer.SGD(learning_rate=0.1)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    step = make_ctr_train_step_packed(
        model, opt, cache_cfg, slot_ids=np.arange(sz.slots),
        batch_size=sz.batch, num_dense=sz.dense, donate=False, amp=False)
    args = (params, opt.init(params), pcache.state, pcache.device_map.state,
            pack_ctr_batch(_lo32(tagged), dense_x, labels))
    with jax.default_matmul_precision("highest"):
        on_dev = step(*args)
        on_cpu = step(*jax.device_put(args, jax.devices("cpu")[0]))
    loss_dev, loss_cpu = float(on_dev[3]), float(on_cpu[3])
    d_params = _max_diff(on_dev[0], on_cpu[0])
    d_cache = _max_diff(on_dev[2], on_cpu[2])
    assert abs(loss_dev - loss_cpu) <= 3e-5 * abs(loss_cpu), (loss_dev, loss_cpu)
    assert d_params <= 1e-6 and d_cache <= 1e-6, (d_params, d_cache)
    pcache.discard_pass()
    return {"loss": [round(r1["loss"], 5), round(r2["loss"], 5)],
            "steps": int(r2["steps"]), "pass_keys": int(len(keys)),
            **_push_facts(t_leg),
            "f32_step_vs_cpu": {"loss": [loss_dev, loss_cpu],
                                "max_abs_params": d_params,
                                "max_abs_cache": d_cache}}


# ---------------------------------------------------------------------------
# stream
# ---------------------------------------------------------------------------


def leg_stream(sz: Sizes) -> Dict:
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.core.profiler import compile_counts
    from paddle_tpu.ps import rpc
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.communicator import SyncCommunicator
    from paddle_tpu.ps.hot_tier import HotTierConfig
    from paddle_tpu.ps.ps_trainer import CtrStreamTrainer
    from paddle_tpu.ps.table import TableConfig

    t_leg = time.perf_counter()
    ds = make_ctr_dataset(sz, sz.stream_batches, seed=2)[0]
    sparse, dense = _slot_names(sz)
    servers = [rpc.NativePsServer(n_trainers=1) for _ in range(2)]
    client = rpc.RpcPsClient([f"127.0.0.1:{s.port}" for s in servers])
    comm = SyncCommunicator(client)
    try:
        client.create_sparse_table(0, TableConfig(
            table_id=0, shard_num=4, accessor="ctr",
            accessor_config=AccessorConfig(embedx_dim=sz.embedx_dim)))
        comm.start()
        pt.seed(0)
        tr = CtrStreamTrainer(
            _deepfm(sz), optimizer.Adam(learning_rate=1e-3), None,
            embedx_dim=sz.embedx_dim, sparse_slots=sparse, dense_slots=dense,
            label_slot="label", communicator=comm, table_id=0,
            hot_tier=HotTierConfig(capacity=sz.capacity))
        tier = tr.hot_tier
        cold = tr.train_from_dataset(ds, batch_size=sz.batch)
        st_cold = tier.stats()
        assert st_cold["misses"] > 0, "the cold epoch admitted nothing"
        client.reset_op_counts()
        before = compile_counts()["requests"]
        warm = tr.train_from_dataset(ds, batch_size=sz.batch)
        rpcs = client.reset_op_counts()
        recompiled = compile_counts()["requests"] - before
        assert cold["steps"] == warm["steps"] == sz.stream_batches, (cold, warm)
        assert np.isfinite(cold["loss"]) and np.isfinite(warm["loss"])
        assert rpcs == {}, f"the warm epoch performed PS RPCs: {rpcs}"
        assert recompiled == 0, f"the warm epoch compiled {recompiled} program(s)"
        st = warm["hot_tier"]
        assert st["misses"] == st_cold["misses"], "the warm epoch missed"

        # rows flushed to the servers equal the tier's, bit for bit
        flushed = tier.flush()
        keys = tier.resident_keys()
        assert flushed == len(keys) == st["occupancy"], \
            (flushed, len(keys), st["occupancy"])
        values, found = tier.table.export_full(keys)
        assert found.all(), "a resident key is missing from the servers"
        rows = tier.device_map.lookup_host(keys)
        dev = {k: np.asarray(v)[rows] for k, v in tier.state.items()}
        srv = tier._full_to_cols(values)
        has = dev["has_embedx"] > 0
        for col in ("show", "click", "embed_w", "embed_state", "has_embedx"):
            np.testing.assert_array_equal(srv[col], dev[col], err_msg=col)
        for col in ("embedx_w", "embedx_state"):
            np.testing.assert_array_equal(srv[col][has], dev[col][has],
                                          err_msg=col)
        assert (dev["show"] >= 2.0).all(), "a resident row was never pushed"
    finally:
        comm.stop()
        client.close()
        for s in servers:
            s.stop()
    return {"loss": [round(cold["loss"], 5), round(warm["loss"], 5)],
            "steps": int(warm["steps"]), "resident_rows": int(len(keys)),
            "warm_rpcs": rpcs, **_push_facts(t_leg)}


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------


def leg_dense(sz: Sizes) -> Dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.ernie import Ernie, ErnieConfig
    from paddle_tpu.ops.flash_attention import flash_attention
    from paddle_tpu.parallel.ring_attention import local_attention

    on_tpu = jax.default_backend() == "tpu"
    pt.seed(0)
    cfg = ErnieConfig(vocab_size=sz.vocab, hidden_size=sz.hidden,
                      num_heads=sz.heads, ffn_size=sz.ffn,
                      num_layers=sz.layers, max_seq_len=sz.seq)
    trainer = Trainer(Ernie(cfg), optimizer.Adam(learning_rate=1e-4),
                      nn.functional.cross_entropy, amp=True)
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, sz.vocab, (sz.ernie_batch, sz.seq)),
                      jnp.int32)
    labels = jnp.asarray(rng.integers(0, sz.vocab, (sz.ernie_batch, sz.seq)),
                         jnp.int32)
    # the program the trainer is about to compile: on a TPU the attention
    # is the Mosaic kernel (a tpu_custom_call per pallas_call), elsewhere
    # the einsum — an interpreted kernel would show as neither
    hlo = trainer._train_step.lower(
        trainer.state, trainer.opt_state, jax.random.key(0), (ids,),
        (labels,)).as_text()
    mosaic_calls = hlo.count("tpu_custom_call")
    assert (mosaic_calls > 0) == on_tpu, \
        f"{mosaic_calls} tpu_custom_call(s) in the step on {jax.default_backend()}"
    losses = [float(trainer.train_step(ids, labels))
              for _ in range(sz.ernie_steps)]
    assert all(np.isfinite(l) for l in losses), losses
    # random labels: the first loss sits at ln(vocab), later ones below it
    assert abs(losses[0] - np.log(sz.vocab)) < 1.0, (losses, np.log(sz.vocab))
    assert losses[-1] < losses[0], losses

    # flash fwd+bwd against local_attention at the model's head shape.
    # Errors are per leaf, relative to that leaf's largest entry.
    # precision="highest" keeps f32 MXU operands in the kernel and in the
    # reference, so what is left is the online-softmax association: the
    # v5e measured 4.0e-5, the bound is 5e-4. The default path rounds
    # q/k/v/p to bf16 inside the kernel (8 mantissa bits through two
    # contractions) against the same f32 reference: measured 7.7e-3,
    # bound 3e-2 (chip run, PR 21).
    D = cfg.head_dim
    q, k, v = (jnp.asarray(rng.normal(size=(2, sz.seq, sz.heads, D)),
                           jnp.float32) for _ in range(3))

    def fwd_bwd(attn):
        """(out, d sum(out²)/d(q, k, v)) of one attention formulation."""
        def f(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out ** 2), out

        def run(q, k, v):
            (_, out), g = jax.value_and_grad(f, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
            return out, g

        return jax.jit(run)

    with jax.default_matmul_precision("highest"):
        ref = fwd_bwd(local_attention)(q, k, v)
        exact = fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, precision="highest"))(q, k, v)
    fast = fwd_bwd(flash_attention)(q, k, v)
    err_exact = _max_diff(exact, ref, relative=True)
    err_fast = _max_diff(fast, ref, relative=True)
    assert err_exact <= 5e-4, f"flash(highest) vs local_attention: {err_exact}"
    assert err_fast <= 3e-2, f"flash(default) vs local_attention: {err_fast}"

    # the same, causal under a sliding window at grouped-query heads (k and
    # v repeated first, as ``models/transformer.py`` hands them over): the
    # pair list's second bound and the kernels' second mask, compiled. The
    # bounds are the bidirectional call's.
    from paddle_tpu.models.transformer import repeat_kv

    H, G, Dw = sz.window_heads
    W = sz.window
    qw = jnp.asarray(rng.normal(size=(1, sz.window_seq, H, Dw)), jnp.float32)
    kw, vw = repeat_kv(*(jnp.asarray(rng.normal(
        size=(1, sz.window_seq, G, Dw)), jnp.float32) for _ in range(2)), H)
    with jax.default_matmul_precision("highest"):
        ref = fwd_bwd(lambda q, k, v: local_attention(
            q, k, v, causal=True, window=W))(qw, kw, vw)
        exact = fwd_bwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=W, precision="highest"))(qw, kw, vw)
    fast = fwd_bwd(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=W))(qw, kw, vw)
    win_exact = _max_diff(exact, ref, relative=True)
    win_fast = _max_diff(fast, ref, relative=True)
    assert win_exact <= 5e-4, f"windowed flash(highest) vs einsum: {win_exact}"
    assert win_fast <= 3e-2, f"windowed flash(default) vs einsum: {win_fast}"

    # and under a STATED mask (``ops/eva.py``): the row's own aligned
    # window beside the chunk summaries of the windows before it, one
    # softmax — the pair list and the kernels' tile from the one statement,
    # through the pooling to φ and μ. The bounds are the bidirectional
    # call's.
    from paddle_tpu.ops import eva

    He, De = sz.eva_heads
    qe, ke, ve = (jnp.asarray(rng.normal(size=(1, sz.eva_seq, He, De)),
                              jnp.float32) for _ in range(3))
    phi, mu = (jnp.asarray(rng.normal(size=(He, De)), jnp.float32)
               for _ in range(2))

    def eva_fwd_bwd(attn):
        def f(*a):
            out = attn(*a, sz.eva_window, sz.eva_chunk)
            return jnp.sum(out ** 2), out

        return jax.jit(lambda *a: jax.value_and_grad(
            f, argnums=(0, 1, 2, 3, 4), has_aux=True)(*a))

    operands = (qe, ke, ve, phi, mu)
    with jax.default_matmul_precision("highest"):
        ref = eva_fwd_bwd(eva.eva_attention_einsum)(*operands)
        exact = eva_fwd_bwd(lambda *a: eva.eva_attention(
            *a, precision="highest"))(*operands)
    fast = eva_fwd_bwd(eva.eva_attention)(*operands)
    eva_exact = _max_diff(exact, ref, relative=True)
    eva_fast = _max_diff(fast, ref, relative=True)
    assert eva_exact <= 5e-4, f"EVA flash(highest) vs einsum: {eva_exact}"
    assert eva_fast <= 3e-2, f"EVA flash(default) vs einsum: {eva_fast}"

    # one hyper-connected sublayer (``transformer.HyperConnected`` round a
    # tanh of the collected stream; the streams side by side as it takes
    # them: its four kernels), forward and backward to the streams
    # and to phi, b and alpha, against the same equations as two einsums
    # with the norm made before the projection: the mappings are float32
    # on the chip too (the projection at precision highest), so the bound
    # is the float32 one.
    from paddle_tpu.models.transformer import HyperConnected
    from paddle_tpu.ops.hyper_connection import sinkhorn

    class _Streams:
        hc_mult, hidden_size = sz.hc_streams, sz.hc_hidden
        hc_sinkhorn_iters, hc_eps, hc_clamp = 20, 1e-6, (-30.0, 30.0)
        rms_eps, init_std = 1e-6, 0.006

    n, C = sz.hc_streams, sz.hc_hidden
    layer = HyperConnected(_Streams)
    xs = jnp.asarray(rng.normal(size=(1, sz.hc_seq, n, C)), jnp.float32)
    hc_params = (layer.phi, layer.b, jnp.full((3,), 0.3, jnp.float32))

    def through_the_layer(xs, params):
        out, _, err = nn.functional_call(
            layer, {"params": dict(zip(("phi", "b", "alpha"), params)),
                    "buffers": {}}, xs.reshape(1, sz.hc_seq, n * C),
            jnp.tanh)[0]
        return jnp.sum(out ** 2), err

    def by_einsum(xs, params):
        phi, b, alpha = params
        flat = xs.reshape(1, sz.hc_seq, n * C)
        z = (flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True)
                             + 1e-6)) @ phi
        h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + b[:n])
        h_post = 2 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + b[n:2 * n])
        h_res = sinkhorn(jnp.clip(
            alpha[2] * z[..., 2 * n:].reshape(1, sz.hc_seq, n, n)
            + b[2 * n:].reshape(n, n), -30.0, 30.0), 20, 1e-6)
        y = jnp.tanh(jnp.einsum("bln,blnc->blc", h_pre, xs))
        out = jnp.einsum("blij,bljc->blic", h_res, xs) \
            + h_post[..., None] * y[:, :, None, :]
        return jnp.sum(out ** 2), jnp.zeros(())

    both = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1),
                                                has_aux=True))
    with jax.default_matmul_precision("highest"):
        (want, _), want_grads = both(by_einsum)(xs, hc_params)
    (got, hc_err), got_grads = both(through_the_layer)(xs, hc_params)
    hc_rel = _max_diff((got, got_grads), (want, want_grads), relative=True)
    assert hc_rel <= 5e-4, f"hyper-connected sublayer vs einsum: {hc_rel}"
    assert float(hc_err) <= 1e-4, f"H_res off doubly stochastic: {hc_err}"
    return {"loss": [round(l, 4) for l in losses],
            "attn_impl": "flash" if on_tpu else "einsum",
            "mosaic_calls": mosaic_calls,
            "flash_rel_err": {"highest": err_exact, "default": err_fast,
                              "window_highest": win_exact,
                              "window_default": win_fast,
                              "eva_highest": eva_exact,
                              "eva_default": eva_fast},
            "hc_rel_err": hc_rel, "hc_res_err": float(hc_err)}


# ---------------------------------------------------------------------------
# four
# ---------------------------------------------------------------------------


def leg_four(sz: Sizes, devices) -> Dict:
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from __graft_entry__ import run_hybrid_step
    from paddle_tpu import optimizer
    from paddle_tpu.core import mesh as mesh_mod
    from paddle_tpu.models.ctr import make_ctr_train_step_from_keys
    from paddle_tpu.ps.embedding_cache import HbmEmbeddingCache
    from paddle_tpu.ps.sharded_cache import (
        make_sharded_ctr_train_step_from_keys, select_routing)

    t_leg = time.perf_counter()
    K = len(devices)
    mesh = mesh_mod.make_mesh({"ps": K}, devices=devices)
    cache_cfg = _cache_cfg(sz)
    rng = np.random.default_rng(4)
    pool = _slot_tagged(
        rng.integers(0, sz.ids_per_slot, size=(2 * sz.batch, sz.slots)))
    keys = pool[:sz.batch]
    lo32 = jnp.asarray(_lo32(keys))
    dense = jnp.asarray(rng.normal(size=(sz.batch, sz.dense)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 2, size=sz.batch), jnp.int32)

    def run(sharded: bool):
        pt.seed(0)
        table = _host_table(sz)
        kw = dict(mesh=mesh, axis="ps") if sharded else {}
        cache = HbmEmbeddingCache(table, cache_cfg, device_map=True, **kw)
        cache.begin_pass(pool.reshape(-1))
        model, opt = _deepfm(sz), optimizer.Adam(learning_rate=1e-3)
        params = {"params": dict(model.named_parameters()), "buffers": {}}
        if sharded:
            step = make_sharded_ctr_train_step_from_keys(
                model, opt, cache_cfg, mesh, slot_ids=np.arange(sz.slots),
                axis="ps", donate=False)
        else:
            step = make_ctr_train_step_from_keys(
                model, opt, cache_cfg, slot_ids=np.arange(sz.slots),
                donate=False)
        out = step(params, opt.init(params), cache.state,
                   cache.device_map.state, lo32, dense, labels)
        cache.state = out[2]
        facts = {"loss": float(out[3])}
        if sharded:
            facts["overflow"] = int(out[4])
            facts["shard_devices"] = min(
                len({s.device for s in leaf.addressable_shards})
                for leaf in jax.tree_util.tree_leaves(cache.state))
        cache.end_pass()
        rows, found = table.export_full(keys.reshape(-1))
        assert found.all()
        return facts, rows, out[0]

    one, rows_one, params_one = run(sharded=False)
    four, rows_four, params_four = run(sharded=True)
    assert four["overflow"] == 0, four
    assert four["shard_devices"] == K, four
    # same step, same f32 inputs, same default matmul precision: the
    # sharded one means over K slices and pmean's, the one-device one
    # means over the batch — summation order only. Four v5e chips
    # measured 8e-8 relative on the loss, 2.8e-7 on the parameters and
    # 7e-12 on the flushed rows (chip run, PR 21).
    assert abs(four["loss"] - one["loss"]) <= 1e-5 * abs(one["loss"]), (one, four)
    d_rows = _max_diff(rows_four, rows_one)
    d_params = _max_diff(params_four, params_one)
    assert d_rows <= 1e-5 and d_params <= 1e-5, (d_rows, d_params)
    routing = select_routing(sz.batch // K * sz.slots, sz.capacity // K, K,
                             cache_cfg.push_mode)
    hybrid_loss = run_hybrid_step(devices)
    return {"loss": [one["loss"], four["loss"]], "overflow": four["overflow"],
            "shard_devices": four["shard_devices"], "routing": list(routing),
            "max_abs_rows": d_rows, "max_abs_params": d_params,
            "hybrid_loss": round(hybrid_loss, 5), **_push_facts(t_leg)}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main() -> int:
    t_start = time.perf_counter()
    # before jax is touched: the native library this process loads is the
    # one it compiles now, here, from the sources in the checkout
    from paddle_tpu.ps.native import build_native, native_available

    t0 = time.perf_counter()
    if not build_native(force=True) or not native_available():
        raise RuntimeError("chip_smoke needs the native library: no `make`")
    print(f"native: rebuilt in {time.perf_counter() - t0:.1f}s", flush=True)

    import jax
    import jaxlib

    from paddle_tpu.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    held = (sum(f.endswith("-cache") for f in os.listdir(cache_dir))
            if os.path.isdir(cache_dir) else 0)
    devices = jax.devices()
    dev = devices[0]
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    print(f"platform: {dev.platform}\ndevice_kind: {dev.device_kind}\n"
          f"device_count: {len(devices)}\njax: {jax.__version__} "
          f"jaxlib: {jaxlib.__version__} libtpu: {libtpu_version}\n"
          f"compile_cache: {cache_dir}", flush=True)
    if dev.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU: jax found platform={dev.platform!r} "
            f"({dev.device_kind})")

    sz = Sizes()
    legs = [("pass", lambda: leg_pass(sz)),
            ("stream", lambda: leg_stream(sz)),
            ("dense", lambda: leg_dense(sz))]
    if len(devices) >= 4:
        legs.append(("four", lambda: leg_four(sz, devices[:4])))
    for name, leg in legs:
        t0 = time.perf_counter()
        facts = leg()
        print(f"{name}: ok {time.perf_counter() - t0:.1f}s "
              f"{json.dumps(facts)}", flush=True)
    if len(devices) < 4:
        print(f"four: skipped, {len(devices)} device(s)", flush=True)

    # jax writes a program to the cache when it took over 1 s to compile
    # (its default threshold), so a second run finds them all; one that
    # compiled in just under a second before may cross the line and be
    # written now
    from paddle_tpu.core.profiler import compile_counts, host_spans

    # the program's own counters: backend compile requests, persistent-
    # cache hits, entries written
    log = compile_counts()
    state = (f"warm: {log['cache_hits']} of the {held} programs the cache "
             f"held were found, {log['cache_written']} written" if held else
             f"cold: the cache was empty, the {log['cache_written']} programs "
             "that took over 1 s to compile were written")
    print(f"cache: requests={log['requests']} hits={log['cache_hits']} "
          f"written={log['cache_written']} — {state}", flush=True)
    compiled = sorted((s for s in host_spans() if s.name == "pt.compile"
                       and not s.counts["hit"]), key=lambda s: -s.dur)[:5]
    print("compiled, not read (costliest): " + json.dumps(
        [[s.counts["fun"], round(s.dur, 2)] for s in compiled]), flush=True)
    print(f"wall: {time.perf_counter() - t_start:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The program's own names: ``pt.*`` scopes in the jitted steps, phase
spans of the pass lifecycle, and the benchmark reader over both
(``benchmarks/harness/scopes.py``)."""

import json
import pathlib
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.core import profiler
from paddle_tpu.core.profiler import DEVICE_SCOPES, RecordEvent, host_spans
from paddle_tpu.ps.accessor import AccessorConfig
from paddle_tpu.ps.embedding_cache import CacheConfig, HbmEmbeddingCache
from paddle_tpu.ps.table import MemorySparseTable, TableConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks"))
sys.path.insert(0, str(ROOT / "tools"))

from harness import scopes, trace  # noqa: E402

S, D, B, DIM = 4, 3, 16, 8


def _table():
    return MemorySparseTable(TableConfig(
        shard_num=2, accessor_config=AccessorConfig(embedx_dim=DIM)))


def _cache_cfg(capacity=256):
    return CacheConfig(capacity=capacity, embedx_dim=DIM,
                       embedx_threshold=0.0, push_mode="dense")


def _keys(n=B * S, pool=50):
    slot = np.arange(S, dtype=np.uint64)[None, :] << np.uint64(32)
    return slot + (np.arange(n, dtype=np.uint64).reshape(-1, S)
                   % np.uint64(pool))


# -- (a) every step of the benchmark names its work ------------------------

def _ctr_parts():
    from paddle_tpu.models.ctr import CtrConfig, DeepFM

    model = DeepFM(CtrConfig(num_sparse_slots=S, num_dense=D, embedx_dim=DIM,
                             dnn_hidden=(16, 16)))
    opt = optimizer.Adam(1e-3)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    return model, opt, params


def _pass_step_text():
    from paddle_tpu.models.ctr import (make_ctr_train_step_slab,
                                       pack_ctr_batch)

    model, opt, params = _ctr_parts()
    keys = _keys()
    cache = HbmEmbeddingCache(_table(), _cache_cfg(), device_map=True)
    cache.begin_pass(keys.reshape(-1))
    step = make_ctr_train_step_slab(
        model, opt, _cache_cfg(), slot_ids=np.arange(S), batch_size=B,
        num_dense=D, slab=2, with_weights=True, amp=True)
    packed = pack_ctr_batch((keys & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                            np.zeros((B, D), np.float32),
                            np.zeros(B, np.int8),
                            weights=np.ones(B, np.uint8))
    return step.lower(params, opt.init(params), cache.state,
                      cache.device_map.state,
                      jnp.asarray(np.stack([packed, packed]))
                      ).compile().as_text()


def _routed_step_text():
    from paddle_tpu.core import mesh as mesh_mod
    from paddle_tpu.ps.sharded_cache import \
        make_sharded_ctr_train_step_from_keys

    model, opt, params = _ctr_parts()
    keys = _keys()
    mesh = mesh_mod.make_mesh({"ps": 4}, devices=jax.devices()[:4])
    cache = HbmEmbeddingCache(_table(), _cache_cfg(), device_map=True,
                              mesh=mesh, axis="ps")
    cache.begin_pass(keys.reshape(-1))
    step = make_sharded_ctr_train_step_from_keys(
        model, opt, _cache_cfg(), slot_ids=np.arange(S), mesh=mesh,
        axis="ps")
    return step.lower(
        params, opt.init(params), cache.state, cache.device_map.state,
        (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        np.zeros((B, D), np.float32), np.zeros(B, np.int32)
    ).compile().as_text()


def _ernie_step_text():
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.ernie import Ernie, ErnieConfig

    model = Ernie(ErnieConfig(vocab_size=128, hidden_size=64, num_heads=2,
                              ffn_size=128, num_layers=2, max_seq_len=128,
                              attn_impl="flash"))
    trainer = Trainer(model, optimizer.Adam(1e-3),
                      nn.functional.cross_entropy, amp=True)
    ids = np.zeros((2, 128), np.int32)
    return trainer.compiled_text(ids, ids)


def _olmoe_step_text():
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.olmoe import Olmoe, OlmoeConfig

    model = Olmoe(OlmoeConfig(vocab_size=128, hidden_size=64, num_heads=2,
                              num_layers=2, num_experts=8,
                              experts_per_token=2, expert_size=32,
                              max_seq_len=128, attn_impl="flash"))
    trainer = Trainer(model, optimizer.AdamW(1e-3, weight_decay=0.1),
                      nn.functional.cross_entropy, amp=True)
    ids = np.zeros((2, 128), np.int32)
    return trainer.compiled_text(ids, ids)


def _joyai_step_text():
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.joyai import Joyai, JoyaiConfig, joyai_loss

    model = Joyai(JoyaiConfig(
        vocab_size=128, hidden_size=64, num_heads=2, num_layers=2,
        dense_size=96, q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8,
        v_dim=16, num_experts=8, experts_per_token=2, expert_size=32,
        held=(2, 2), max_seq_len=128, attn_impl="flash"))
    trainer = Trainer(model, optimizer.AdamW(1e-3, weight_decay=0.1),
                      joyai_loss, amp=True)
    ids = np.zeros((2, 128), np.int32)
    profiler.start_timeline()
    text = trainer.compiled_text(ids, ids)
    held = [s.counts for s in profiler.host_spans()
            if s.name == "pt.moe.held"]
    assert held == [{"first": 2, "count": 2, "experts": 8}]   # once a trace
    return text


def _lfm2_step_text():
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.lfm2 import Lfm2, Lfm2Config, lfm2_loss

    model = Lfm2(Lfm2Config(
        vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=2,
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        dense_size=96, num_experts=8, experts_per_token=2, expert_size=32,
        held=(2, 2), max_seq_len=128, attn_impl="flash"))
    trainer = Trainer(model, optimizer.AdamW(1e-3, weight_decay=0.1),
                      lfm2_loss, amp=True)
    ids = np.zeros((2, 128), np.int32)
    profiler.start_timeline()
    text = trainer.compiled_text(ids, ids)
    spans = {s.name: s.counts for s in profiler.host_spans()
             if s.name in ("pt.lfm2.layers", "pt.moe.held")}
    assert [s.name for s in profiler.host_spans()].count(
        "pt.lfm2.layers") == 1                              # once a trace
    assert spans == {"pt.lfm2.layers": {"conv": 2, "attention": 1,
                                        "dense": 1, "experts": 2},
                     "pt.moe.held": {"first": 2, "count": 2, "experts": 8}}
    return text


def _smallthinker_step_text():
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.smallthinker import (SmallThinker,
                                                SmallThinkerConfig,
                                                smallthinker_loss)

    model = SmallThinker(SmallThinkerConfig(
        vocab_size=128, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, sliding_window_size=48, num_layers=2, router_width=8,
        experts_per_token=2, expert_size=32, held=(2, 2), max_seq_len=128,
        attn_impl="flash", recompute="experts"))
    trainer = Trainer(model, optimizer.AdamW(1e-3, weight_decay=0.1),
                      smallthinker_loss, amp=True)
    ids = np.zeros((2, 128), np.int32)
    profiler.start_timeline()
    text = trainer.compiled_text(ids, ids)
    names = [s.name for s in profiler.host_spans()]
    assert names.count("pt.smallthinker.layers") == 1       # once a trace
    spans = {s.name: s.counts for s in profiler.host_spans()
             if s.name in ("pt.smallthinker.layers", "pt.moe.held")}
    assert spans == {
        "pt.smallthinker.layers": {"full": 1, "window": 1, "experts": 2,
                                   "window_size": 48},
        "pt.moe.held": {"first": 2, "count": 2, "experts": 8}}
    # one flash call a layer: the global one, then the windowed one
    assert [(s.counts["window"], s.counts["pairs_walked"],
             s.counts["pairs_rectangle"]) for s in profiler.host_spans()
            if s.name == "pt.flash.operands"] == [(0, 1, 1), (48, 1, 1)]
    return text


def _evabyte_step_text():
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.evabyte import (EvaByte, EvaByteConfig,
                                           evabyte_loss)

    model = EvaByte(EvaByteConfig(
        vocab_size=320, hidden_size=64, num_heads=4, intermediate_size=160,
        num_layers=2, window_size=32, chunk_size=4, num_pred_heads=2,
        max_seq_len=128, attn_impl="flash", recompute="blocks"))
    trainer = Trainer(model, optimizer.AdamW(1e-3, weight_decay=0.1),
                      evabyte_loss, amp=True)
    ids = np.zeros((2, 128), np.int32)
    profiler.start_timeline()
    text = trainer.compiled_text(ids, ids)
    spans = [s.counts for s in profiler.host_spans()
             if s.name == "pt.eva.layers"]
    assert spans == [{"layers": 2, "window": 32, "chunk": 4,
                      "summaries": 24, "pred_heads": 2}]     # once a trace
    # one flash call a layer (its recomputation is the same call's trace):
    # 4 windows of one 32-block each and the 24 summaries in a block of
    # their own — 4 local pairs + 3 of summaries, of the 4 x 5 rectangle
    assert {(s.counts["summary_keys"], s.counts["pairs_walked"],
             s.counts["pairs_rectangle"]) for s in profiler.host_spans()
            if s.name == "pt.flash.operands"} == {(24, 7, 20)}
    return text


def _xing4_step_text():
    from paddle_tpu.executor import Trainer
    from paddle_tpu.models.joyai import Joyai, JoyaiConfig
    from paddle_tpu.models.transformer import next_token_loss

    model = Joyai(JoyaiConfig(
        vocab_size=128, hidden_size=64, num_heads=2, num_layers=2,
        dense_size=96, q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8,
        v_dim=16, num_experts=8, experts_per_token=2, expert_size=32,
        held=(2, 2), max_seq_len=128, attn_impl="flash", num_mtp=0,
        hc_mult=4, recompute="blocks", rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 8}))
    trainer = Trainer(model, optimizer.AdamW(1e-3, weight_decay=0.1),
                      next_token_loss, amp=True)
    ids = np.zeros((2, 128), np.int32)
    profiler.start_timeline()
    text = trainer.compiled_text(ids, ids)
    spans = [s.counts for s in profiler.host_spans()
             if s.name == "pt.hc.layers"]
    assert spans == [{"layers": 2, "streams": 4, "sinkhorn_iters": 20,
                      "sublayers": 4}]                       # once a trace
    return text


_PUSH = {"pt.push.accumulate", "pt.push.update"}
STEPS = {
    "pass_slab": (_pass_step_text, {"pt.unpack", "pt.probe", "pt.pull",
                                    "pt.tower", "pt.dense_opt"} | _PUSH),
    "routed_4dev": (_routed_step_text, {"pt.probe", "pt.pull", "pt.tower",
                                        "pt.dense_opt", "pt.route"} | _PUSH),
    "ernie": (_ernie_step_text, {"pt.embed", "pt.attn", "pt.ffn",
                                 "pt.head_loss", "pt.loss", "pt.dense_opt",
                                 "pt.flash_fwd", "pt.flash_bwd_dq",
                                 "pt.flash_bwd_dkv"}),
    "olmoe": (_olmoe_step_text, {"pt.embed", "pt.attn", "pt.rope", "pt.ffn",
                                 "pt.moe.route", "pt.moe.dispatch",
                                 "pt.moe.experts", "pt.moe.combine",
                                 "pt.head_loss", "pt.loss", "pt.dense_opt",
                                 "pt.flash_fwd", "pt.flash_bwd_dq",
                                 "pt.flash_bwd_dkv"}),
    "joyai": (_joyai_step_text, {"pt.embed", "pt.attn", "pt.mla.q",
                                 "pt.mla.kv", "pt.rope", "pt.ffn",
                                 "pt.ffn.dense", "pt.moe.route", "pt.moe.dispatch",
                                 "pt.moe.experts", "pt.moe.combine",
                                 "pt.moe.shared", "pt.mtp", "pt.head_loss",
                                 "pt.loss", "pt.dense_opt", "pt.flash_fwd",
                                 "pt.flash_bwd_dq", "pt.flash_bwd_dkv"}),
    # the residual path's three scopes inside each sublayer's own; no
    # prediction module, so no ``pt.mtp``
    "xing4": (_xing4_step_text, {"pt.embed", "pt.attn", "pt.mla.q",
                                 "pt.mla.kv", "pt.rope", "pt.ffn",
                                 "pt.ffn.dense", "pt.moe.route",
                                 "pt.moe.dispatch", "pt.moe.experts",
                                 "pt.moe.combine", "pt.moe.shared",
                                 "pt.hc.map", "pt.hc.collect",
                                 "pt.hc.scatter", "pt.head_loss", "pt.loss",
                                 "pt.dense_opt", "pt.flash_fwd",
                                 "pt.flash_bwd_dq", "pt.flash_bwd_dkv"}),
    "lfm2": (_lfm2_step_text, {"pt.embed", "pt.conv", "pt.conv.in",
                               "pt.conv.mix", "pt.conv.out", "pt.attn",
                               "pt.gqa.qkv", "pt.rope", "pt.gqa.repeat",
                               "pt.ffn", "pt.ffn.dense", "pt.moe.route",
                               "pt.moe.dispatch", "pt.moe.experts",
                               "pt.moe.combine", "pt.head_loss", "pt.loss",
                               "pt.dense_opt", "pt.flash_fwd",
                               "pt.flash_bwd_dq", "pt.flash_bwd_dkv"}),
    # ``pt.attn.full`` / ``pt.attn.window`` sit OUTSIDE ``pt.attn``: never
    # an operation's last token (test_attention_kinds_are_read_off_...)
    "smallthinker": (_smallthinker_step_text, {
        "pt.embed", "pt.attn", "pt.gqa.qkv", "pt.rope", "pt.gqa.repeat",
        "pt.ffn", "pt.moe.route", "pt.moe.dispatch", "pt.moe.experts",
        "pt.moe.combine", "pt.head_loss", "pt.loss", "pt.dense_opt",
        "pt.flash_fwd", "pt.flash_bwd_dq", "pt.flash_bwd_dkv"}),
    "evabyte": (_evabyte_step_text, {
        "pt.embed", "pt.attn", "pt.eva.qkv", "pt.rope", "pt.eva.prep",
        "pt.ffn.dense", "pt.head_loss", "pt.loss", "pt.dense_opt",
        "pt.flash_fwd", "pt.flash_bwd_dq", "pt.flash_bwd_dkv"}),
}
# what computes nothing (and what XLA inserts without metadata), and the
# collectives, which carry no scope by design
_NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "copy", "broadcast", "iota"}
_OPCODE_RE = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.*?\s"
                        r"([a-z][\w\-]*)\(")
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_CONVERT_OF = re.compile(r"\sconvert\(%?([\w.\-]+)\)")
_COLLECTIVE_OP = re.compile(r"/(all_to_all|psum|all_gather|axis_index|"
                            r"psum_scatter|pmean)$")


@pytest.mark.parametrize("name", sorted(STEPS))
def test_step_names_its_work(name):
    build, want = STEPS[name]
    assert want <= set(DEVICE_SCOPES)
    text = build()
    seen = {s for s in scopes.scope_of_ops(text).values() if s}
    assert want <= seen, sorted(want - seen)
    assert seen <= set(DEVICE_SCOPES), sorted(seen - set(DEVICE_SCOPES))
    # A convert XLA made itself (no metadata) is counted — it is work — in
    # the scope of what it converts. XLA:CPU has no bf16 storage ops: the
    # Pallas interpreter slices every block of a bf16 operand out of the
    # loop's carry and writes it back each grid step, and XLA:CPU widens
    # and narrows round each of those slices. No other convert comes
    # without metadata: where no kernel operand is bf16 the rule changes
    # nothing (ERNIE step before PR 27: 3179 / 3344 with it and without).
    op_name, converts = {}, {}
    for line in text.splitlines():
        m = _NAME_RE.match(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            op_name[m.group(1)] = op.group(1) if op else None
            src = _CONVERT_OF.search(line)
            if src and not op:
                converts[m.group(1)] = src.group(1)
    scoped = total = 0
    for line in text.splitlines():
        m = _OPCODE_RE.match(line)
        if not m or m.group(1) in _NO_WORK:
            continue
        name = _NAME_RE.match(line).group(1)
        while op_name.get(name) is None and name in converts:
            name = converts[name]
        op = op_name.get(name)
        if op and _COLLECTIVE_OP.search(op):
            continue
        total += 1
        scoped += bool(op and scopes.scope_of(op))
    assert total > 100 and scoped / total >= 0.90, (scoped, total)


@pytest.mark.parametrize("model,causal", [("olmoe", True), ("ernie", False)])
def test_flash_operand_span_counts_a_models_block_pairs(model, causal):
    """``pt.flash.operands`` in a model's step (PR 41): still one span a
    ``flash_attention`` call a trace — two layers, two spans, the backward
    adds none — and each says how many of its head's 2 x 2 block pairs the
    grid walks: the causal decoder's list leaves one out, the bidirectional
    encoder's rectangle none."""
    from paddle_tpu.executor import make_train_step

    if model == "olmoe":
        from paddle_tpu.models.olmoe import Olmoe, OlmoeConfig

        net = Olmoe(OlmoeConfig(vocab_size=128, hidden_size=64, num_heads=2,
                                num_layers=2, num_experts=8,
                                experts_per_token=2, expert_size=32,
                                max_seq_len=1024, attn_impl="flash"))
    else:
        from paddle_tpu.models.ernie import Ernie, ErnieConfig

        net = Ernie(ErnieConfig(vocab_size=128, hidden_size=64, num_heads=2,
                                ffn_size=128, num_layers=2, max_seq_len=1024,
                                attn_impl="flash"))
    opt = optimizer.Adam(1e-3)
    step = make_train_step(net, opt, nn.functional.cross_entropy, amp=True)
    state = nn.get_state(net)
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), tree)
    ids = (jax.ShapeDtypeStruct((1, 1024), jnp.int32),)
    profiler.start_timeline()
    step.trace(shapes(state), shapes(jax.eval_shape(opt.init, state["params"])),
               jax.ShapeDtypeStruct((), jax.random.key(0).dtype), ids, ids)
    got = [(s.counts["pairs_walked"], s.counts["pairs_rectangle"])
           for s in host_spans() if s.name == "pt.flash.operands"]
    assert got == [(3 if causal else 4, 4)] * 2


@pytest.mark.parametrize("kind", ["pt.attn.full", "pt.attn.window"])
def test_attention_kinds_are_read_off_the_whole_name(kind):
    """``benchmarks/harness/scope_paths.py`` (PR 44): the kind of an
    attention block is a scope OUTSIDE ``pt.attn``, so the last-token
    readers see ``pt.attn`` / ``pt.flash_*`` as in every other model, and
    the kind's reader finds, under each kind, all three kernels (forward
    and transposed), the projections and the repeat — and no operation of
    the router, which runs before the block, or of the other kind."""
    from harness import scope_paths

    assert kind in DEVICE_SCOPES
    text = _smallthinker_step_text()
    under = scope_paths.ops_under(text, kind)
    other = scope_paths.ops_under(text, ({"pt.attn.full", "pt.attn.window"}
                                         - {kind}).pop())
    last = scopes.scope_of_ops(text)
    mine = {last[name] for name, inside in under.items() if inside}
    assert {"pt.attn", "pt.gqa.qkv", "pt.gqa.repeat", "pt.flash_fwd",
            "pt.flash_bwd_dq", "pt.flash_bwd_dkv"} <= mine, sorted(mine)
    assert ("pt.rope" in mine) == (kind == "pt.attn.window")
    assert not mine & {"pt.moe.route", "pt.moe.experts", "pt.head_loss",
                       "pt.embed", "pt.dense_opt"}
    assert not any(under[name] and other[name] for name in under)
    assert not any(s == kind for s in last.values())    # never the last


def test_every_pallas_call_is_named():
    src = ROOT / "paddle_tpu"
    calls = 0
    for path in src.rglob("*.py"):
        text = path.read_text()
        for m in re.finditer(r"pl\.pallas_call\(", text):
            depth, i = 0, m.end() - 1
            while True:          # the call's own argument list
                depth += {"(": 1, ")": -1}.get(text[i], 0)
                if depth == 0:
                    break
                i += 1
            assert re.search(r"\bname=\"\w+\"", text[m.end():i]), (
                path, text[m.start():m.start() + 80])
            calls += 1
    # the three flash-attention kernels and the residual path's four
    assert calls == 7


# -- (b) RecordEvent: ids, parents, counts, a bounded ring ----------------

def test_record_event_parents_and_counts():
    profiler.start_timeline()
    with RecordEvent("outer", keys=3) as ev:
        with RecordEvent("inner_a"):
            pass
        with RecordEvent("inner_b", rows=2.5):
            with RecordEvent("leaf"):
                pass
        ev["bytes"] = 40            # a count known only after the work
    by = {s.name: s for s in host_spans()}
    assert [s.name for s in host_spans()] == ["inner_a", "leaf", "inner_b",
                                              "outer"]
    assert by["outer"].parent_id == 0
    assert by["inner_a"].parent_id == by["outer"].span_id
    assert by["inner_b"].parent_id == by["outer"].span_id
    assert by["leaf"].parent_id == by["inner_b"].span_id
    assert by["outer"].counts == {"keys": 3, "bytes": 40}
    assert by["inner_b"].counts == {"rows": 2.5}
    assert len({s.span_id for s in host_spans()}) == 4
    assert by["inner_a"].dur + by["inner_b"].dur <= by["outer"].dur
    assert by["outer"].t0 <= by["inner_a"].t0


def test_record_event_parent_is_per_thread():
    profiler.start_timeline()
    inside = threading.Event()
    release = threading.Event()

    def worker():
        with RecordEvent("worker_root"):
            with RecordEvent("worker_child"):
                inside.set()
                release.wait(5)

    t = threading.Thread(target=worker)
    with RecordEvent("main_root"):
        t.start()
        assert inside.wait(5)
        with RecordEvent("main_child"):   # opened while the worker's are
            pass
        release.set()
        t.join()
    by = {s.name: s for s in host_spans()}
    assert by["worker_root"].parent_id == 0       # not main_root's child
    assert by["worker_child"].parent_id == by["worker_root"].span_id
    assert by["main_child"].parent_id == by["main_root"].span_id
    assert by["worker_root"].tid != by["main_root"].tid


def test_record_event_survives_an_exception():
    profiler.start_timeline()
    with pytest.raises(ValueError):
        with RecordEvent("fails"):
            raise ValueError("x")
    with RecordEvent("after"):
        pass
    by = {s.name: s for s in host_spans()}
    assert by["after"].parent_id == 0     # the failed scope was popped


def test_span_ring_is_bounded_and_always_on():
    for i in range(profiler.SPAN_RING + 10):
        with RecordEvent("tick"):
            pass
    spans = host_spans()
    assert len(spans) == profiler.SPAN_RING
    assert spans[-1].span_id - spans[0].span_id >= profiler.SPAN_RING - 1
    profiler.start_timeline()
    assert host_spans() == []


def test_chrome_export_carries_counts_and_merges(tmp_path):
    from timeline import merge_traces

    profiler.start_timeline()
    with RecordEvent("pt.pass.upload", bytes=128):
        pass
    path = profiler.export_chrome_tracing(str(tmp_path / "w0.json"))
    blob = json.load(open(path))
    (ev,) = blob["traceEvents"]
    assert ev["ph"] == "X" and ev["args"]["bytes"] == 128
    assert ev["args"]["parent_id"] == 0 and "clockSyncUs" in blob
    n = merge_traces([path], str(tmp_path / "merged.json"))
    merged = json.load(open(tmp_path / "merged.json"))["traceEvents"]
    assert n == len(merged)
    assert "pt.pass.upload" in {e["name"] for e in merged}


def test_removed_profiler_names_are_gone():
    import paddle_tpu.core as core

    for name in ("CostTimer", "start_profiler", "stop_profiler",
                 "profiler_enabled", "stop_timeline", "record_event"):
        assert not hasattr(profiler, name) and not hasattr(core, name)


# -- (c) the pass lifecycle's span tree ------------------------------------

# implicit rows (the slot table fits the cache): the map is built first
# and its placement names the rows; explicit rows: the index names them
# and the map stores them
BEGIN = {1: ["pt.pass.dedup", "pt.pass.map_build", "pt.pass.index",
             "pt.pass.export", "pt.pass.layout", "pt.pass.upload"],
         0: ["pt.pass.dedup", "pt.pass.index", "pt.pass.map_build",
             "pt.pass.export", "pt.pass.layout", "pt.pass.upload"]}
END = ["pt.pass.fetch", "pt.pass.flush_index", "pt.pass.flush_export",
       "pt.pass.merge", "pt.pass.import"]


def _children(spans, root):
    return [s for s in spans if s.parent_id == root.span_id]


@pytest.mark.parametrize("capacity,implicit", [(256, 1), (128, 0)])
def test_pass_lifecycle_span_tree(capacity, implicit):
    """256 rows hold the 256-slot map of the pass's keys (implicit rows);
    128 rows do not (explicit rows)."""
    profiler.start_timeline()
    table = _table()
    cache = HbmEmbeddingCache(table, _cache_cfg(capacity), device_map=True)
    keys = _keys().reshape(-1)
    n = cache.begin_pass(keys)
    state_bytes = sum(a.nbytes for a in cache.state.values())
    cache_map_state = dict(cache.device_map.state)
    map_bytes = sum(a.nbytes for a in cache_map_state.values())
    cache.end_pass()
    spans = host_spans()
    (begin,) = [s for s in spans if s.name == "pt.pass.begin"]
    (end,) = [s for s in spans if s.name == "pt.pass.end"]
    assert begin.parent_id == 0 and end.parent_id == 0
    kids = _children(spans, begin)
    assert [s.name for s in kids] == BEGIN[implicit]
    assert ("row" in cache_map_state) == (not implicit)
    assert [s.name for s in _children(spans, end)] == END
    for root in (begin, end):
        assert sum(s.dur for s in _children(spans, root)) <= root.dur
    uniq = len(np.unique(keys))
    assert n == uniq
    assert begin.counts == {"keys": len(keys), "unique_keys": uniq,
                            "capacity": capacity, "shards": 1,
                            "implicit_rows": implicit}
    assert end.counts == {"keys": uniq}
    by = {s.name: s for s in spans}
    full_dim = table.export_full(keys[:1])[0].shape[1]
    assert by["pt.pass.export"].counts == {"bytes": uniq * full_dim * 4}
    assert by["pt.pass.layout"].counts == {"bytes": state_bytes}
    assert by["pt.pass.upload"].counts == {"bytes": state_bytes + map_bytes}
    assert by["pt.pass.fetch"].counts == {"bytes": state_bytes}
    # the table's own RecordEvent nests one level further down
    assert by["pserver_sparse_export_full"].parent_id in {
        by["pt.pass.export"].span_id, by["pt.pass.flush_export"].span_id}


def test_prepare_and_activate_apart_make_two_roots():
    profiler.start_timeline()
    cache = HbmEmbeddingCache(_table(), _cache_cfg(), device_map=True)
    prepared = cache.prepare_pass(_keys().reshape(-1))
    with RecordEvent("ctr_pass_build"):       # as CtrPassTrainer wraps it
        cache.activate_pass(prepared)
    cache.discard_pass()
    spans = host_spans()
    by = {s.name: s for s in spans}
    assert "pt.pass.begin" not in by
    assert by["pt.pass.prepare"].parent_id == 0
    assert by["pt.pass.prepare"].counts == {"keys": B * S,
                                            "unique_keys": B * S}
    assert [s.name for s in _children(spans, by["pt.pass.prepare"])] \
        == BEGIN[1][:3]
    assert by["pt.pass.activate"].parent_id == by["ctr_pass_build"].span_id
    assert by["pt.pass.activate"].counts == {
        "unique_keys": B * S, "capacity": 256, "shards": 1,
        "implicit_rows": 1}
    assert [s.name for s in _children(spans, by["pt.pass.activate"])] \
        == BEGIN[1][3:]


def test_pass_phases_picks_the_larger_pass():
    profiler.start_timeline()
    small = HbmEmbeddingCache(_table(), _cache_cfg(64), device_map=True)
    big = HbmEmbeddingCache(_table(), _cache_cfg(), device_map=True)
    small.begin_pass(_keys(16, pool=3).reshape(-1))      # the check's cache
    big.begin_pass(_keys().reshape(-1))                  # the cell's pass
    small.end_pass()
    big.end_pass()
    spans = host_spans()
    roots = [s for s in spans if s.name == "pt.pass.begin"]
    assert sorted(r.counts["unique_keys"] for r in roots) == [12, B * S]
    want = max(roots, key=lambda r: r.counts["unique_keys"])
    assert scopes.pick_root(spans, "begin") is want
    got = scopes.pass_phases("begin")
    kids = {s.name[len("pt.pass."):]: s.dur for s in _children(spans, want)}
    assert {k: v for k, v in got.items() if k != "_root"} == kids
    assert got["_root"] == want.dur
    assert scopes.pick_root(spans, "end").counts == {"keys": B * S}
    assert scopes.phase_seconds("begin", "dedup", "index", "map_build") \
        == kids["dedup"] + kids["index"] + kids["map_build"]
    assert scopes.phase_seconds("end", "no_such_phase") is None
    profiler.start_timeline()
    assert scopes.pass_phases("begin") is None           # nothing recorded


# -- (d) the reader on a recorded pair --------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(ROOT / "benchmarks" / "testdata" / "scoped_trace.json") as f:
        return json.load(f)


def test_scope_of_ops_on_recorded_text(recorded):
    got = scopes.scope_of_ops(recorded["hlo_text"])
    for name, want in recorded["expect"]["scope_of_ops"].items():
        assert got[name] == want, name
    assert scopes.scope_of("jit(f)/transpose(jvp(pt.tower))/mul") \
        == "pt.tower"
    assert scopes.scope_of("jit(f)/pt.attn/pt.flash_fwd/x") == "pt.flash_fwd"
    assert scopes.scope_of("jit(f)/pt.push.update/select_n") \
        == "pt.push.update"
    assert scopes.scope_of("jit(f)/script.py/opt.step/add") is None


def test_scope_shares_on_recorded_trace(recorded):
    red = trace.reduce_trace(recorded["events"])
    got = scopes.shares_of(red["op_self_s"],
                           scopes.scope_of_ops(recorded["hlo_text"]))
    want = recorded["expect"]["shares"]
    assert set(got["shares"]) == set(want)
    for k, v in want.items():
        assert got["shares"][k] == pytest.approx(v, rel=1e-9), k
    assert sum(got["shares"].values()) == pytest.approx(1.0)
    assert [[a, pytest.approx(b)] for a, b in
            recorded["expect"]["unscoped_ops"]] == got["unscoped_ops"]


def test_share_readers_on_recorded_trace(recorded, capsys):
    class System:
        def compiled_text(self):
            return recorded["hlo_text"]

    ctx = {"trace": trace.reduce_trace(recorded["events"]), "hlo_text": "",
           "system": System()}
    assert scopes.share(ctx, "pt.push.accumulate", "pt.push.update") \
        == pytest.approx(8 / 25)
    assert scopes.share(ctx, "pt.attn", prefix="pt.flash_") \
        == pytest.approx(2 / 25)
    assert scopes.share(ctx, "pt.embed") == 0.0       # read, and not there
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["unscoped_ops"][0][0] == "copy.5 f32[64,8]"
    assert line["scoped_ops"][0] == ["fusion.1 f32[8,16]", "pt.tower",
                                     pytest.approx(0.008)]
    # no trace, or a step whose text names no scope: None, never 0
    assert scopes.share({"trace": None}, "pt.tower") is None
    bare = re.sub(r", metadata=\{[^}]*\}", "", recorded["hlo_text"])

    class Unscoped:
        def compiled_text(self):
            return bare

    none = {"trace": ctx["trace"], "hlo_text": bare, "system": Unscoped()}
    assert scopes.share(none, "pt.tower") is None
    assert "no share reported" in capsys.readouterr().err

"""Compile for the chip without a chip.

``jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")`` hands back four ``TPU v5 lite`` devices that
need no hardware, and ``jit(f).lower(<ShapeDtypeStruct on them>).compile()``
runs XLA:TPU and the real Mosaic compiler. So every Pallas entry point
(the three flash-attention kernels) is compiled here with
``interpret=False`` on every PR (tier-1, ~1 s each): a kernel Mosaic
refuses fails without spending chip time. The sparse path has no kernel
(``PERF.md`` section 6, PR 28); its steps are compiled here as XLA:TPU
makes them, for every rule.

Marked ``slow``: the four steps ``chip_smoke.py`` runs, at its widths,
with ``jax.default_backend`` patched to "tpu" so every ``auto`` switch
takes the branch the chip takes.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

pytest.importorskip("libtpu", reason="compiling for TPU needs libtpu")

from paddle_tpu.ops.flash_attention import flash_attention  # noqa: E402
from paddle_tpu.ops.sparse_optimizer import rule_state_dim  # noqa: E402
from paddle_tpu.ps.embedding_cache import CacheConfig  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (the widths and the DeepFM the smoke runs)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    assert len(devices) == 4 and devices[0].device_kind == "TPU v5 lite"
    return devices


def _shapes(tree, sharding=None):
    """The arrays of ``tree`` as ShapeDtypeStructs (placed by ``sharding``):
    compiling needs shapes alone."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _compile(fn, sharding, *args):
    return jax.jit(fn).lower(*_shapes(args, sharding)).compile()


def _rng_key(sharding=None):
    return jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=sharding)


def _z(*shape, dtype=jnp.float32):
    return np.zeros(shape, dtype)


def _rows(C, xd, rule="adagrad"):
    """The seven columns of a cache or tier of ``C`` rows under ``rule``."""
    return {"show": _z(C), "click": _z(C), "embed_w": _z(C, 1),
            "embed_state": _z(C, rule_state_dim(rule, 1)),
            "embedx_w": _z(C, xd),
            "embedx_state": _z(C, rule_state_dim(rule, xd)),
            "has_embedx": _z(C)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_fwd_bwd_compiles(v5e, dtype):
    q = _z(2, 512, 12, 64, dtype=dtype)   # ERNIE-1.0 base's head shape

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: flash_attention(
            q, k, v, interpret=False).astype(jnp.float32).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, q).as_text()
    assert hlo.count("tpu_custom_call") == 3   # fwd, dq, dk/dv


def test_flash_attention_causal_head128_seq4096_compiles(v5e):
    """OLMoE's attention shape: causal, head dim 128 (no pad to the lane
    width), 4096 positions, bf16-operand kernels on float32 arrays."""
    q = _z(2, 4096, 16, 128)

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, q).as_text()
    assert hlo.count("tpu_custom_call") == 3


@pytest.mark.parametrize("shape,causal", [((32, 512, 12, 64), False),
                                          ((2, 4096, 16, 128), True)])
def test_flash_kernels_are_handed_bf16_and_one_statistics_array(
        v5e, shape, causal):
    """The two dense cells' attention shapes, float32 in and out: as the
    chip compiles them, every ``flash_*`` custom call takes q, k, v (and
    do) as ``bf16[BH, L, 128]`` — the convert rides in the fusion that
    writes the operand — and each backward call exactly one float32
    ``[BH, L, 128]`` array, lse and delta in its lanes 0 and 1."""
    import re

    q = _z(*shape)

    def fwd_bwd(q, k, v):
        # a cotangent that is not a constant, as a model's is
        loss = lambda q, k, v: (flash_attention(
            q, k, v, causal=causal, interpret=False) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, q).as_text()
    B, L, H, _ = shape
    wide = f"[{B * H},{L},128]"
    calls = dict(re.findall(
        r"%(flash_[a-z_]+)[.\d]* = .*?operand_layout_constraints=\{(.*?)\}, \w+=",
        hlo))
    assert set(calls) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    # a causal call's pair list (PR 41): 36 of the 8 x 8 block pairs, as
    # three scalar-prefetch tables beside the offsets
    tables = ["s32[36]"] * 3 if causal else []
    for name, operands in calls.items():
        got = re.findall(r"(\w+\[[\d,]*\])", operands)
        n = 3 if name == "flash_fwd" else 4
        stats = [] if name == "flash_fwd" else ["f32" + wide]
        assert got == ["s32[4]"] + tables + ["bf16" + wide] * n + stats, (
            name, got)


def _fingerprint(text):
    """A compiled module's text without what follows the checkout and its
    line numbers: the ``FileNames`` / ``FunctionNames`` / ``FileLocations``
    / ``StackFrames`` tables, each instruction's ``metadata`` and
    ``stack_frame_id``, and a kernel's ``backend_config`` (Mosaic's
    bytecode embeds the source file's path; between PR 29's tree and PR
    30's the bodies differed in that path alone). Shapes, layouts,
    opcodes, operands and instruction names stay."""
    import hashlib
    import re

    keep, skip = [], False
    for line in text.splitlines():
        if re.match(r"^(FileNames|FunctionNames|FileLocations|StackFrames)",
                    line):
            skip = True
        if skip:
            skip = line.strip() != ""
            continue
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        line = re.sub(r"stack_frame_id=\d+", "", line)
        line = re.sub(r'backend_config="[^"]*"', "", line)
        keep.append(re.sub(r"backend_config=\{.*\}", "", line))
    return hashlib.sha256("\n".join(keep).encode()).hexdigest()[:16]


def _renumbered(text):
    """``text`` with every instruction's number (``%fusion.12``) replaced by
    the order in which its name first appears. XLA:TPU numbers the two
    results it reads off each of SmallThinker's recomputed ``conditional``s
    in either order from one compile of the SAME tree to the next (four
    texts of PR 49's tree, alike but for ``%get-tuple-element.1292`` and
    ``.1294`` trading places): what is compared is the program, not the
    counter."""
    import re

    seen = {}
    return re.sub(r"%[\w.\-]+\.\d+\b",
                  lambda m: seen.setdefault(m.group(0), f"%i{len(seen)}"),
                  text)


@pytest.mark.parametrize("shape,causal,want", [
    ((32, 512, 12, 64), False, "6ff7e9f446afbeed"),
    ((2, 4096, 16, 128), True, "81fbae24ce38b455")])
def test_equal_width_flash_compiles_to_the_program_of_pr29(
        v5e, shape, causal, want):
    """``flash_attention`` grew a value width of its own (PR 30). Where q,
    k and v are equally wide — both older dense cells — the chip's
    compiler must get the program it got from PR 29's tree: the
    fingerprints were taken from that tree (``git archive``) and from
    this one, and were equal. A PR that means to change these programs
    re-takes them and says so in ``PERF.md``. PR 41 did, for the causal
    call alone: its kernels walk a pair list (three more scalar operands,
    a two-dimensional grid; ``09b7d1997ba9f9a7`` before). The
    bidirectional call's is still PR 29's: a call the list does not serve
    compiles to the program it always did."""
    q = _z(*shape)

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: (flash_attention(
            q, k, v, causal=causal, interpret=False) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, q).as_text()
    assert _fingerprint(text) == want


def test_dropless_moe_without_a_held_range_compiles_to_the_program_of_pr29(
        v5e, as_tpu):
    """``parallel/moe.py`` grew ``held_moe`` beside ``dropless_moe`` and a
    tile rule for widths no tile divides (PR 30). OLMoE's layer, called as
    before, compiles to PR 29's program (fingerprint taken from both
    trees), and its tiles are the ones of before."""
    from paddle_tpu import amp
    from paddle_tpu.ops.grouped_matmul import _fit_tile
    from paddle_tpu.parallel import moe

    for tile, dim in ((1024, 2048), (1024, 1024), (512, 2048), (512, 1024),
                      (1024, 128), (512, 16)):
        assert _fit_tile(tile, dim) == min(tile, dim)
    assert _fit_tile(512, 768) == 384 and _fit_tile(1024, 768) == 768
    T, d, E, k, f = 1024, 256, 8, 2, 128

    def fwd_bwd(x, router, w_gate, w_up, w_down):
        with amp.auto_cast(True):
            loss = lambda *a: jnp.sum(moe.dropless_moe(*a, k)[0] ** 2)
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
                x, router, w_gate, w_up, w_down)

    text = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), _z(T, d),
                    _z(d, E), _z(E, d, f), _z(E, d, f), _z(E, f, d)).as_text()
    assert _fingerprint(text) == "a968e134d9ba2770"
    assert "conditional" not in text


def test_flash_qk192_v128_lowers_and_v_is_not_padded_to_q(v5e):
    """JoyAI-LLM-Flash's attention shape — causal, 32 heads, q.k at 192,
    P.v at 128, 4096 positions — as Mosaic compiles it: q and k are handed
    256 lanes wide (192 is no multiple of 128), v, dO, the output and dv
    128: the value side is never padded to q's width."""
    import re

    B, L, H = 1, 4096, 32
    q, v = _z(B, L, H, 192), _z(B, L, H, 128)

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: (flash_attention(
            q, k, v, causal=True, interpret=False) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, v).as_text()
    found = re.findall(
        r"%(flash_[a-z_]+)[.\d]* = (.*?) custom-call\(.*?"
        r"operand_layout_constraints=\{(.*?)\}, \w+=", hlo)
    calls = {name: operands for name, _, operands in found}
    made = {name: result for name, result, _ in found}
    assert set(calls) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    qk, val = f"bf16[{B * H},{L},256]", f"bf16[{B * H},{L},128]"
    stats = f"f32[{B * H},{L},128]"
    operands = {n: re.findall(r"(\w+\[[\d,]*\])", c)
                for n, c in calls.items()}
    scalars = ["s32[4]"] + ["s32[36]"] * 3      # offsets, the pair list
    assert operands["flash_fwd"] == scalars + [qk, qk, val]
    for name in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert operands[name] == scalars + [qk, qk, val, val, stats], name
    results = lambda name: re.findall(r"(\w+\[[\d,]*\])", made[name])
    wide, narrow = f"f32[{B * H},{L},256]", f"f32[{B * H},{L},128]"
    assert results("flash_fwd") == [narrow, stats]
    assert results("flash_bwd_dq") == [wide]
    assert results("flash_bwd_dkv") == [wide, narrow]


@pytest.mark.parametrize("cell,shape,dv", [
    ("olmoe_1b7b_seq4096 window", (2, 4096, 16, 128), 128),
    ("olmoe_1b7b_seq4096 check", (1, 4096, 16, 128), 128),
    ("joyai_flash_seq4096 window", (2, 4096, 32, 192), 128),
    ("joyai_flash_seq4096 check", (1, 4096, 32, 192), 128),
    ("lfm2_8b_a1b_seq4096 window and check", (4, 4096, 32, 64), 64)])
def test_causal_pair_list_kernels_lower_at_the_cells_shapes(
        v5e, cell, shape, dv):
    """The three causal cells' attention calls, at their windows' shapes
    and at their checks' (OLMoE's and JoyAI's checks run one sequence), as
    Mosaic compiles them with the pair list (PR 41): each of the three
    kernels reads the 36 walked pairs of the 8 x 8 rectangle from three
    scalar-prefetch tables beside the offsets."""
    import re

    q, v = _z(*shape), _z(*shape[:3], dv)

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: (flash_attention(
            q, k, v, causal=True, interpret=False) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, v).as_text()
    calls = dict(re.findall(
        r"%(flash_[a-z_]+)[.\d]* = .*?operand_layout_constraints=\{(.*?)\}, \w+=",
        hlo))
    assert set(calls) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    for name, operands in calls.items():
        got = re.findall(r"(\w+\[[\d,]*\])", operands)
        assert got[:4] == ["s32[4]"] + ["s32[36]"] * 3, (name, got)


@pytest.mark.parametrize("window,pairs", [(None, 528), (4096, 252)])
def test_windowed_pair_list_kernels_lower_at_the_cells_shape(
        v5e, window, pairs):
    """``smallthinker_21b_seq16384``'s two attention calls (PR 44) — one
    16,384-token sequence, 28 heads of 128 — as Mosaic compiles them: the
    global layer's kernels read the 528 causal pairs of the 32 x 32
    rectangle from their tables, a windowed layer's the 252 its 4096-key
    band leaves."""
    import re

    q = _z(1, 16384, 28, 128)

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: (flash_attention(
            q, k, v, causal=True, window=window, interpret=False) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    hlo = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, q).as_text()
    calls = dict(re.findall(
        r"%(flash_[a-z_]+)[.\d]* = .*?operand_layout_constraints=\{(.*?)\}, \w+=",
        hlo))
    assert set(calls) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    for name, operands in calls.items():
        got = re.findall(r"(\w+\[[\d,]*\])", operands)
        assert got[:4] == ["s32[4]"] + [f"s32[{pairs}]"] * 3, (name, got)


@pytest.mark.parametrize("window", [4096, 5000])
def test_a_window_that_binds_nowhere_compiles_to_the_causal_program(
        v5e, window):
    """A window as long as the sequence is no window (PR 44): the call
    compiles to the causal call's program, the one every accepted cell's
    attention compiles to (the fingerprint of
    ``test_equal_width_flash_compiles_to_the_program_of_pr29``)."""
    q = _z(2, 4096, 16, 128)

    def fwd_bwd(q, k, v):
        loss = lambda q, k, v: (flash_attention(
            q, k, v, causal=True, window=window, interpret=False) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, q).as_text()
    assert _fingerprint(text) == "81fbae24ce38b455"


@pytest.mark.parametrize("precision,operand", [("default", "bf16"),
                                               ("highest", "f32")])
def test_eva_mask_kernels_lower_at_the_cells_shape(v5e, precision, operand):
    """``evabyte_6b5_seq8192``'s attention call (PR 46) — one 8192-byte
    sequence, 32 heads of 128, windows of 2048 in chunks of 16 — as Mosaic
    compiles it under the stated mask: the three kernels read the 52 pairs
    the mask leaves of the 16 x 17 rectangle from their tables, over 8704
    key columns (one block of 384 summaries, padded, then the keys). With
    float32 operands too: what the cell's check runs its float32 side
    through."""
    import re

    from paddle_tpu.ops import eva

    q, p = _z(1, 8192, 32, 128), _z(32, 128)

    def fwd_bwd(q, k, v, phi, mu):
        loss = lambda *a: (eva.eva_attention(
            *a, 2048, 16, interpret=False, precision=precision) ** 2).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
            q, k, v, phi, mu)

    hlo = _compile(fwd_bwd, SingleDeviceSharding(v5e[0]), q, q, q, p,
                   p).as_text()
    calls = dict(re.findall(
        r"%(flash_[a-z_]+)[.\d]* = .*?operand_layout_constraints=\{(.*?)\}, \w+=",
        hlo))
    assert set(calls) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    for name, operands in calls.items():
        got = re.findall(r"(\w+\[[\d,]*\])", operands)
        assert got[:4] == ["s32[4]"] + ["s32[52]"] * 3, (name, got)
        assert got[4:7] == [f"{operand}[32,8192,128]"] \
            + [f"{operand}[32,8704,128]"] * 2, (name, got)
    assert "pt.eva.prep" in hlo


def _evabyte_step(v5e, cfg, seq):
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.evabyte import EvaByte, evabyte_loss

    model = EvaByte(cfg)
    opt = optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1, beta2=0.95)
    step = make_train_step(model, opt, evabyte_loss, amp=True)
    state = nn.get_state(model)
    ids = (_z(1, seq, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    return step.lower(
        _shapes(state, s),
        _shapes(jax.eval_shape(opt.init, state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile()


def test_evabyte_step_compiles_small(v5e, as_tpu):
    """The EvaByte train step for the chip at small widths with the
    published head, window and chunk sizes over two windows: four kernel
    calls a layer (the forward, its recomputation, the two backward
    kernels), every new scope in the text, the 3 + 1 pairs of the 2 x 3
    rectangle in the tables."""
    from paddle_tpu.models.evabyte import EvaByteConfig

    text = _evabyte_step(v5e, EvaByteConfig(
        hidden_size=256, num_heads=2, intermediate_size=512, num_layers=2,
        num_pred_heads=8, recompute="blocks"), seq=4096).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * 4
    for scope in ("pt.eva.qkv", "pt.eva.prep", "pt.rope", "pt.ffn.dense",
                  "pt.head_loss"):
        assert scope in text, scope
    # blocks of 512 in windows of 2048: 2 x 10 local pairs and the second
    # window's 4 q blocks on the one summary block, of the 8 x 9 rectangle
    assert "s32[24]" in text
    assert _fingerprint(text) == "72ac98038cf18e85"


@pytest.mark.slow
def test_evabyte_cell_step_compiles(v5e, as_tpu):
    """The benchmark cell's step at full widths (four layers, one
    8192-byte sequence, blocks recomputed): 12.45 GiB for a v5e, 9.18 of
    it parameters and moments (PERF.md section 6, PR 46)."""
    from paddle_tpu.models.evabyte import EvaByteConfig

    m = _evabyte_step(v5e, EvaByteConfig(
        num_layers=4, total_layers=32, recompute="blocks"),
        seq=8192).memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 12.0 * 2**30 < live < 13.5 * 2**30


def test_smallthinker_step_compiles_small(v5e, as_tpu):
    """The SmallThinker train step for the chip at small widths with the
    published head shape (14 query heads on 2 key-value heads of 128) and
    a window that binds: three flash kernels a layer under each mask, the
    two forms' ``conditional`` in each expert layer — forward, its
    recomputation and the backward's own — every new scope in the text."""
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.smallthinker import (SmallThinker,
                                                SmallThinkerConfig,
                                                smallthinker_loss)

    model = SmallThinker(SmallThinkerConfig(
        vocab_size=1024, hidden_size=256, num_heads=14, num_kv_heads=2,
        head_dim=128, sliding_window_size=512, num_layers=2,
        router_width=16, experts_per_token=4, expert_size=768, held=(4, 2),
        max_seq_len=2048, recompute="experts"))
    opt = optimizer.AdamW(learning_rate=4e-4, weight_decay=0.1, beta2=0.95)
    step = make_train_step(model, opt, smallthinker_loss, amp=True)
    state = nn.get_state(model)
    ids = (_z(1, 2048, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    text = step.lower(
        _shapes(state, s),
        _shapes(jax.eval_shape(opt.init, state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 2 * 3
    assert text.count(" conditional(") >= 2 * 2
    for scope in ("pt.attn.full", "pt.attn.window", "pt.gqa.qkv",
                  "pt.gqa.repeat", "pt.rope", "pt.moe.route",
                  "pt.moe.experts"):
        assert scope in text, scope
    # the global call's 4 x 4 causal list, the windowed call's band of it
    assert "s32[10]" in text and "s32[7]" in text
    assert _fingerprint(_renumbered(text)) == "e5c0662c83ec68eb"


def test_joyai_step_compiles_small(v5e, as_tpu):
    """The JoyAI-LLM-Flash train step for the chip at small widths with
    the published head widths (192 / 128): three flash kernels a block
    (one dense, one expert, the prediction module's), the two forms'
    ``conditional`` forward and backward in each expert layer, every new
    scope in the text."""
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.joyai import Joyai, JoyaiConfig, joyai_loss

    model = Joyai(JoyaiConfig(
        vocab_size=1024, hidden_size=256, num_heads=2, num_layers=2,
        dense_size=512, q_rank=192, kv_rank=128, num_experts=16,
        experts_per_token=4, expert_size=768, held=(4, 2), max_seq_len=512))
    opt = optimizer.AdamW(learning_rate=4e-4, weight_decay=0.1, beta2=0.95)
    step = make_train_step(model, opt, joyai_loss, amp=True)
    state = nn.get_state(model)
    ids = (_z(2, 512, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    text = step.lower(
        _shapes(state, s),
        _shapes(jax.eval_shape(opt.init, state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3 * 3
    assert text.count(" conditional(") == 2 * 2
    for scope in ("pt.mla.q", "pt.mla.kv", "pt.rope", "pt.moe.shared",
                  "pt.moe.experts", "pt.mtp"):
        assert scope in text, scope
    assert _fingerprint(text) == "0ff95bd6e8144bd1"


def test_xing4_step_compiles_small(v5e, as_tpu):
    """``models/joyai.py`` as Xing4.0 runs it — four residual streams,
    YaRN, no prediction module, every block recomputed — compiles for the
    chip at small widths with the published head widths (192 / 128): the
    forward kernel twice a block (forward and its recomputation) and each
    backward kernel once, the two forms' ``conditional`` forward,
    recomputed and backward in the expert layer, the residual path's three
    scopes in the text and its four kernels compiled by Mosaic at their
    counts, the streams never laid out anew between them."""
    import re

    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.joyai import Joyai, JoyaiConfig
    from paddle_tpu.models.transformer import next_token_loss

    model = Joyai(JoyaiConfig(
        vocab_size=1024, hidden_size=256, num_heads=2, num_layers=2,
        dense_size=512, q_rank=192, kv_rank=128, num_experts=16,
        experts_per_token=4, expert_size=768, held=(4, 2), max_seq_len=512,
        num_mtp=0, hc_mult=4, recompute="blocks", rope_theta=10000.0,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096}))
    opt = optimizer.AdamW(learning_rate=4e-4, weight_decay=0.1, beta2=0.95)
    step = make_train_step(model, opt, next_token_loss, amp=True)
    state = nn.get_state(model)
    ids = (_z(2, 512, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    text = step.lower(
        _shapes(state, s),
        _shapes(jax.eval_shape(opt.init, state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile().as_text()
    calls = re.findall(r"%(flash_[a-z_]+)[.\d]* = ", text)
    assert sorted(calls) == ["flash_bwd_dkv"] * 2 + ["flash_bwd_dq"] * 2 \
        + ["flash_fwd"] * 4
    assert text.count(" conditional(") == 3
    for scope in ("pt.hc.map", "pt.hc.collect", "pt.hc.scatter", "pt.mla.q",
                  "pt.mla.kv", "pt.rope", "pt.moe.shared", "pt.moe.experts",
                  "pt.ffn.dense"):
        assert scope in text, scope
    assert "pt.mtp" not in text
    # the residual path of 4 sublayers: each kernel a Mosaic call under its
    # own scope, forward, rebuilt and backward (the last sublayer's scatter
    # of a block is not rebuilt: nothing in the backward reads it)
    kernels = re.findall(
        r'%(hc_[a-z_]+)[.\d]* = .*custom_call_target="tpu_custom_call".*'
        r'op_name="[^"]*/(pt\.hc\.[a-z]+)/hc_[a-z_]+/pallas_call"', text)
    assert sorted(set(kernels)) == [
        ("hc_post_bwd", "pt.hc.scatter"), ("hc_post_fwd", "pt.hc.scatter"),
        ("hc_pre_bwd", "pt.hc.collect"), ("hc_pre_fwd", "pt.hc.collect")]
    count = lambda name: sum(k == name for k, _ in kernels)
    assert [count(k) for k in ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd",
                               "hc_pre_bwd")] == [8, 6, 4, 4]
    # the streams stay [tokens, n C] from kernel to kernel: nothing lays
    # them out anew (as [.., 4, 256] they would be tiled (4, 128))
    assert "f32[2,512,4,256]" not in text
    assert not re.search(r"f32\[(1024,1024|2,512,1024)\]\S* "
                         r"(copy|transpose|reshape)\(", text)
    # and no matmul is left under pt.hc.map: the projection is the
    # kernels', float32 at precision highest
    assert not [line for line in text.splitlines() if "pt.hc.map" in line
                and re.search(r"\b(dot|convolution)\(", line)]


@pytest.mark.parametrize("tokens", [4096, 4100])
def test_hyper_connection_kernels_compile_at_the_cells_widths(v5e, as_tpu,
                                                              tokens):
    """The residual path's four kernels as ``xing4_29b_a4b_seq4096`` runs
    them — 4,096 tokens, four float32 streams of 3,584 — through Mosaic
    for the described v5e: the projection a float32 matmul at precision
    ``highest`` inside ``hc_pre_fwd`` and twice inside ``hc_pre_bwd`` (Phi
    as [24, n C], its gradient summed over the token grid), the tiles'
    VMEM under the limit the kernels state; and at 4,100 tokens, where the
    last tile is a partial one and ``hc_pre_bwd`` leaves its rows out."""
    from paddle_tpu.ops.hyper_connection import hc_gates, hc_post, hc_pre

    n, c = 4, 3584

    def both_ways(x, phi, b, alpha, ct):
        def path(x, phi, b, alpha):
            u, z, x = hc_pre(x, phi, b, alpha, 1e-6)
            _, h_post, h_res = hc_gates(z, b, alpha, n, 20, 1e-6,
                                        (-30.0, 30.0))
            return hc_post(x, u, h_post, h_res)

        out, back = jax.vjp(path, x, phi, b, alpha)
        return out, back(ct)

    x = _z(tokens, n, c)
    text = _compile(both_ways, SingleDeviceSharding(v5e[0]), x,
                    _z(n * c, 24), _z(24), _z(3), x).as_text()
    calls = re.findall(r"%(hc_[a-z_]+)[.\d]* = ", text)
    assert sorted(calls) == ["hc_post_bwd", "hc_post_fwd", "hc_pre_bwd",
                             "hc_pre_fwd"], calls


@pytest.mark.slow
def test_xing4_cell_step_fits_the_chip(v5e, as_tpu):
    """The cell's own step (``benchmarks/configs/xing4.0-29b-a4b.json``
    through its adapter's ``_model_cfg``: published widths, 4096 tokens,
    blocks recomputed, float32 streams) compiles for a described v5e to
    12.44 GiB (CPU, PR 51) — under ISSUE 51's 15.0 GiB rule. The model is
    built under ``jax.eval_shape``: shapes alone, no 3 GB of weights."""
    import json
    import os
    import sys

    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.joyai import Joyai
    from paddle_tpu.models.transformer import next_token_loss

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    sys.path.insert(0, bench)
    from harness import spec

    with open(os.path.join(bench, "configs", "xing4.0-29b-a4b.json")) as f:
        cfg = json.load(f)
    model_cfg = spec.load_module(
        "adapters", "causal_mhc_mla_moe_lm")._model_cfg(cfg)
    made = []

    def make():
        made.append(Joyai(model_cfg))
        return nn.get_state(made[0])

    state = jax.eval_shape(make)
    opt = optimizer.AdamW(learning_rate=1e-8, weight_decay=0.1, beta2=0.95)
    step = make_train_step(made[0], opt, next_token_loss, amp=True)
    s = SingleDeviceSharding(v5e[0])
    ids = (_z(cfg["sizes"]["batch_per_chip"], 4096, dtype=jnp.int32),)
    compiled = step.lower(
        _shapes(state, s),
        _shapes(jax.eval_shape(opt.init, state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert 11.5 * 2**30 < total <= 15.0 * 2**30, total / 2**30


def test_lfm2_step_compiles_small(v5e, as_tpu):
    """The LFM2 train step for the chip at small widths with the published
    head shape (4 query heads on 1 key-value head of 64, causal: the
    kernels' first causal call at head width 64): three flash kernels for
    its one attention block, the two forms' ``conditional`` forward and
    backward in each expert layer, every new scope in the text."""
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.lfm2 import Lfm2, Lfm2Config, lfm2_loss

    model = Lfm2(Lfm2Config(
        vocab_size=1024, hidden_size=256, num_heads=4, num_kv_heads=1,
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        dense_size=512, num_experts=8, experts_per_token=4, expert_size=256,
        held=(4, 2), max_seq_len=512))
    opt = optimizer.AdamW(learning_rate=4e-4, weight_decay=0.1, beta2=0.95)
    step = make_train_step(model, opt, lfm2_loss, amp=True)
    state = nn.get_state(model)
    ids = (_z(2, 512, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    text = step.lower(
        _shapes(state, s),
        _shapes(jax.eval_shape(opt.init, state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 3
    assert text.count(" conditional(") == 2 * 2
    for scope in ("pt.conv.in", "pt.conv.mix", "pt.conv.out", "pt.gqa.qkv",
                  "pt.gqa.repeat", "pt.rope", "pt.moe.experts",
                  "pt.ffn.dense"):
        assert scope in text, scope
    assert "bf16[8,512,128]" in text      # q as handed: 64 in 128 lanes
    assert _fingerprint(text) == "8865ead0363f6972"


@pytest.mark.parametrize("cell", ["lfm2", "joyai"])
def test_held_row_movement_walks_chunks_at_the_cells_shapes(v5e, cell):
    """``held_moe``'s bounded buffer at the two held cells' full shapes
    (LFM2: 16,384 tokens, 4 of 32, 8 held, 32,768 rows; JoyAI: 8,192, 8 of
    256, 16 held, 8,192 rows; width 2048, bf16 rows in, f32 rows back):
    both movements and their transposes compile for the chip as loops
    over chunks whose bodies carry the layer's scopes and write their
    chunk in place, the buffers are allocated and never filled whole."""
    import re

    from paddle_tpu.parallel import moe

    T, k, E, count = {"lfm2": (16384, 4, 32, 8),
                      "joyai": (8192, 8, 256, 16)}[cell]
    R, d = moe.dispatch_ladder(T, k, E, count)[0], 2048

    def both_ways(index, x, z, g_buf, g_out):
        held = index < count
        order, _ = moe.sort_by_expert(jnp.where(held, index, count))
        plan = moe._held_plan(order, held, jnp.sum(held, dtype=jnp.int32),
                              R, k)
        buf, to_x = jax.vjp(lambda x: moe._gather_held(x, plan, k), x)
        out, to_z = jax.vjp(lambda z: moe._combine_held(z, plan, k), z)
        return buf, out, to_x(g_buf)[0], to_z(g_out)[0]

    text = _compile(both_ways, SingleDeviceSharding(v5e[0]),
                    _z(T, k, dtype=jnp.int32), _z(T, d, dtype=jnp.bfloat16),
                    _z(R, d), _z(R, d, dtype=jnp.bfloat16),
                    _z(T, d)).as_text()
    # two gathers (the dead chunks zeroed by a loop of their own), two sums
    assert text.count(" while(") == 2 * 2 + 2
    assert text.count('custom_call_target="AllocateBuffer"') == 4
    filled = [m.group(0) for m in re.finditer(
        r"\w+\[([\d,]+)\]\S* broadcast\(", text)
        if np.prod([int(n) for n in m.group(1).split(",")]) >= R * d]
    assert not filled, filled
    # a chunk is written in place by the operation that made it: the
    # update is the root of a fusion, under the layer's scope
    writes = [line for line in text.splitlines()
              if " dynamic-update-slice(" in line and "while/body" in line]
    assert len(writes) == 6, writes
    for line in writes:
        assert line.lstrip().startswith("ROOT "), line
        assert ("while/body/pt.moe.dispatch" in line
                or "while/body/pt.moe.combine" in line), line


def test_ernie_layer_moves_its_bf16_under_a_name(v5e, as_tpu):
    """One ERNIE layer of the benchmark cell's widths, as the chip compiles
    its train step: every copy, convert and fusion of the entry computation
    that writes bf16 carries a ``pt.*`` scope. The narrowing of QKV is the
    model's own op (``mxu_rounded`` under ``pt.attn``); a convert XLA hoists
    there by itself has no metadata, and the layout copy made from it then
    reads as unscoped device time (PERF.md §6, PR 27)."""
    import re

    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.ernie import Ernie, ErnieConfig

    model = Ernie(ErnieConfig(vocab_size=1024, hidden_size=768, num_heads=12,
                              ffn_size=3072, num_layers=1, max_seq_len=512))
    opt = optimizer.Adam(learning_rate=1e-4)
    step = make_train_step(model, opt, nn.functional.cross_entropy, amp=True)
    state = nn.get_state(model)
    ids = (_z(32, 512, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    text = step.lower(
        _shapes(state, s), _shapes(opt.init(state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    wrote = re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (bf16\[[\d,]+\])\S* "
        r"(copy|convert|fusion)\((.*)$", entry, re.M)
    assert any(shape == "bf16[32,512,2304]" and op == "copy"
               for _, shape, op, _ in wrote)      # the QKV layout copy
    unnamed = [(name, shape) for name, shape, _, rest in wrote
               if not re.search(r'op_name="[^"]*pt\.[a-z_]+', rest)]
    assert not unnamed, unnamed
    assert _fingerprint(text) == "3ba011626fe11616"


def _olmoe_step(v5e, cfg, batch, seq):
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.olmoe import Olmoe

    model = Olmoe(cfg)
    opt = optimizer.AdamW(learning_rate=4e-4, weight_decay=0.1, beta2=0.95)
    step = make_train_step(model, opt, nn.functional.cross_entropy, amp=True)
    state = nn.get_state(model)
    ids = (_z(batch, seq, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    return step.lower(
        _shapes(state, s),
        _shapes(jax.eval_shape(opt.init, state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile()


def test_olmoe_step_compiles_small(v5e, as_tpu):
    """The OLMoE train step for the chip at small widths with the
    published head dim: three flash kernels a layer, and XLA:TPU takes the
    grouped matmuls (``ragged_dot`` forward and both transposes), the sort
    by expert and the row gathers as written."""
    from paddle_tpu.models.olmoe import OlmoeConfig

    compiled = _olmoe_step(v5e, OlmoeConfig(
        vocab_size=1024, hidden_size=256, num_heads=2, num_layers=2,
        num_experts=8, experts_per_token=2, expert_size=128,
        max_seq_len=512), batch=2, seq=512)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3 * 2
    assert "pt.moe.experts" in text and "pt.rope" in text
    assert _fingerprint(text) == "1a7b3d0b244845dd"


@pytest.mark.slow
def test_olmoe_cell_step_compiles(v5e, as_tpu):
    """The benchmark cell's step at full widths (one layer, 2 x 4096
    tokens): fits a v5e beside its 7 GiB of parameters and moments."""
    from paddle_tpu.models.olmoe import OlmoeConfig

    m = _olmoe_step(v5e, OlmoeConfig(num_layers=1), batch=2,
                    seq=4096).memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert live < 14.5 * 2**30


# ---------------------------------------------------------------------------
def _linear_bwd_bench():
    """``tools/linear_bwd_bench.py`` under a name of its own (a bare
    ``import`` of a tool collides across test files)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_linear_bwd_bench_tool",
        os.path.join(REPO, "tools", "linear_bwd_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tool_step_fusions(v5e, case, batch, hidden, other, variant):
    """(every fusion of the tool's ``case`` step under ``variant`` as the
    described chip compiles it, its weight gradients' fusions); ``batch``:
    (sequences, length)."""
    tool = _linear_bwd_bench()
    step, arguments, weights = tool.build(case, batch, hidden, other,
                                          variant)
    shapes = _shapes(jax.eval_shape(arguments, jax.random.key(0)),
                     SingleDeviceSharding(v5e[0]))
    found = tool.fusions(step.lower(*shapes).compile().as_text())
    return found, tool.weight_gradient_fusions(found, weights)


def _operand_dtypes(fusions):
    return {o.split(":")[0] for f in fusions for conv in f["convolutions"]
            for o in conv}


@pytest.mark.parametrize("variant,f32_operands,round_trips",
                         [("tree", False, 0), ("parent", True, 14)])
def test_amp_linear_backward_matmuls_read_bf16_buffers(
        v5e, variant, f32_operands, round_trips):
    """The tool's two-block decoder stack at EvaByte's widths (4096 /
    11008; a sequence of 2048, blocks recomputed, AdamW) as the chip
    compiles its step: with ``F.linear``'s stated backward every matmul of
    the step — the forward's, both of the backward — has bf16 operands,
    and no weight gradient is rounded to bf16 and widened again before
    AdamW reads it. The parent's expression, beside it, shows what the
    parser sees where that is not so: float32 cotangents into the backward
    matmuls and every weight's gradient through a bf16 round trip."""
    found, weight_gradients = _tool_step_fusions(
        v5e, "evabyte_block", (1, 2048), 4096, 11008, variant)
    matmuls = [f for f in found.values() if f["convolutions"]]
    # a block: 7 forward, 7 recomputed, 7 dx, 7 dW
    assert len(matmuls) >= 2 * 28 - 2, len(matmuls)
    assert _operand_dtypes(matmuls) == (
        {"bf16", "f32"} if f32_operands else {"bf16"})
    assert sum(f["round_trips"] for f in matmuls) == round_trips
    assert len(weight_gradients) == 2 * 7


@pytest.mark.parametrize("variant,operands,round_trips",
                         [("tree", {"bf16"}, 0), ("parent", {"bf16", "f32"}, 1)])
def test_lm_head_weight_gradient_reads_two_bf16_operands(
        v5e, variant, operands, round_trips):
    """The tool's ``smallthinker_head`` (the final norm, the ``[2560,
    18992]`` head, the float32 cross-entropy, AdamW; 16,384 tokens) as the
    chip compiles its step: through ``F.lm_head`` the weight gradient's
    matmul reads two bf16 operands and its result meets AdamW unrounded;
    the parent's expression rebuilds the float32 softmax gradient inside
    that matmul and rounds the result to bf16 and back."""
    _, weight_gradients = _tool_step_fusions(
        v5e, "smallthinker_head", (1, 16384), 2560, 18992, variant)
    (dw,) = weight_gradients.values()
    assert dw["result"] == "f32[2560,18992]"
    assert _operand_dtypes([dw]) == operands
    assert dw["round_trips"] == round_trips


@pytest.mark.parametrize("wanted,weight_gradients", [((0,), 0), ((0, 1), 2)])
def test_amp_linear_frozen_weight_costs_no_weight_gradient(
        v5e, wanted, weight_gradients):
    """The stated backward hands ``dx`` on through a barrier it shares
    with ``dW``. Where nobody asks for the weight's gradient (a frozen
    layer, a gradient with respect to the input alone) that barrier does
    NOT keep the ``dW`` matmul in the program: two layers of 4096 x 4096
    compile to their forward and ``dx`` matmuls and nothing of a weight's
    shape."""
    from paddle_tpu import amp
    from paddle_tpu.nn import functional as F

    def loss(x, w):
        with amp.auto_cast(enable=True):
            return jnp.sum(jnp.sin(F.linear(jnp.tanh(F.linear(x, w)), w)))

    sharding = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((2048, 4096), jnp.float32, sharding=sharding)
    w = jax.ShapeDtypeStruct((4096, 4096), jnp.float32, sharding=sharding)
    text = jax.jit(jax.grad(loss, wanted)).lower(x, w).compile().as_text()
    matmuls = re.findall(r"= (\w+\[[0-9,]*\])\S* convolution\(", text)
    assert len(matmuls) == 4 + weight_gradients, matmuls
    assert matmuls.count("f32[4096,4096]") == weight_gradients


# the steps chip_smoke.py runs, at its widths, as the chip compiles them
# ---------------------------------------------------------------------------


@pytest.fixture
def as_tpu(monkeypatch):
    """Every ``auto`` switch keys on ``jax.default_backend()``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


SZ = chip_smoke.Sizes()


def _deepfm_and_adam():
    from paddle_tpu import optimizer

    model = chip_smoke._deepfm(SZ)
    return (model, optimizer.Adam(learning_rate=1e-3),
            {"params": dict(model.named_parameters()), "buffers": {}})


def _cmap(nb, capacity=None):
    """Shapes of a per-pass cuckoo map of ``nb`` buckets
    (``ps/device_hash.DeviceKeyMap.state``) over a cache of ``capacity``
    rows: implicit rows where the slot table fits it (the default: a
    cache of exactly the slots, as in both DeepFM cells), else explicit."""
    cmap = {"key": _z(nb, 8, dtype=jnp.uint32), "seed": np.uint32(0)}
    if capacity is not None and nb * 4 > capacity:
        return dict(cmap, row=_z(nb, 4, dtype=jnp.int32))
    return dict(cmap, shard_shift=np.int32(0), shard_rows=np.int32(nb * 4))


def _ctr_state(n_keys):
    """Shapes of a pass cache + cuckoo map at the smoke's widths."""
    from paddle_tpu.ps.device_hash import DeviceKeyMap

    C, xd = SZ.capacity, SZ.embedx_dim
    return _rows(C, xd), _cmap(DeviceKeyMap.buckets_for(n_keys), C)


@pytest.mark.parametrize("form,widths,map_words", [
    ("implicit", ["8", "8"], 8),
    ("explicit", ["4", "4", "8", "8"], 8 + 4)])
def test_probe_bucket_gathers_and_the_map_is_unpadded(v5e, form, widths,
                                                      map_words):
    """The pass cell's probe (2^24 buckets, 106,496 keys a step) as
    XLA:TPU makes it: ``key`` u32[nb, 8] takes 8 x 128 tiles with the
    bucket index minor, so HBM holds exactly its 512 MiB (a [nb, 12] map
    would pad to 16 columns). With implicit rows — what the cell's pass
    builds, 2^26 slots in 2^26 rows — ``pt.probe`` is two gather
    fusions, both 8 wide, and the map's state is ``key`` and three
    scalars; with explicit rows (a fuller cache) ``row`` s32[nb, 4]
    joins it and ``pt.probe`` is four: two 8 wide, two 4 wide."""
    import re

    from paddle_tpu.ps.device_hash import device_hash_lookup

    nb, n = 1 << 24, 106496
    cmap = _cmap(nb, nb * 4 if form == "implicit" else nb * 3)
    assert ("row" in cmap) == (form == "explicit")
    keys = _z(n, dtype=jnp.uint32)
    compiled = _compile(device_hash_lookup, SingleDeviceSharding(v5e[0]),
                        cmap, keys, keys)
    hlo = compiled.as_text()
    assert f"u32[{nb},8]{{0,1:T(8,128)}}" in hlo
    assert (f"s32[{nb},4]{{0,1:T(4,128)}}" in hlo) == (form == "explicit")
    assert compiled.memory_analysis().argument_size_in_bytes \
        <= nb * map_words * 4 + 2 * n * 4 + 4096
    gathers = re.findall(
        r"= [us]32\[%d,(\d)\]\S* fusion\([^\n]*kind=kCustom[^\n]*"
        r"op_name=\"[^\"]*pt\.probe[^\"]*gather\"" % n, hlo)
    assert sorted(gathers) == widths, gathers


@pytest.mark.slow
def test_pass_step_compiles(v5e, as_tpu):
    from paddle_tpu.models.ctr import _packed_layout, make_ctr_train_step_slab

    model, opt, params = _deepfm_and_adam()
    step = make_ctr_train_step_slab(
        model, opt, chip_smoke._cache_cfg(SZ), slot_ids=np.arange(SZ.slots),
        batch_size=SZ.batch, num_dense=SZ.dense, slab=SZ.slab,
        with_weights=True, amp=True)
    cache, cmap = _ctr_state(1 << 20)
    total = _packed_layout(SZ.batch, SZ.slots, SZ.dense, True)[3]
    _compile(step, SingleDeviceSharding(v5e[0]), params, opt.init(params),
             cache, cmap, _z(SZ.slab, total, dtype=jnp.uint8))


@pytest.mark.slow
def test_stream_step_compiles(v5e, as_tpu):
    from paddle_tpu.ps.device_hash import DynamicDeviceKeyMap
    from paddle_tpu.ps.hot_tier import make_hot_ctr_train_step

    model, opt, params = _deepfm_and_adam()
    cfg = CacheConfig(capacity=SZ.capacity, embedx_dim=SZ.embedx_dim)
    dmap = DynamicDeviceKeyMap(SZ.capacity)
    step = make_hot_ctr_train_step(
        model, opt, cfg, slot_ids=np.arange(SZ.slots),
        probe_buckets=dmap.probe_buckets, banks=dmap.banks)
    tier, _ = _ctr_state(1)
    hlo = _compile(step, SingleDeviceSharding(v5e[0]), params, opt.init(params),
                   tier, dmap.device_state(),
                   _z(SZ.batch, SZ.slots, dtype=jnp.uint32),
                   _z(SZ.batch, SZ.dense), _z(SZ.batch, dtype=jnp.int32)
                   ).as_text()
    assert "tpu_custom_call" not in hlo   # the sparse path has no kernel


@pytest.mark.slow
def test_dense_step_compiles(v5e, as_tpu):
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import make_train_step
    from paddle_tpu.models.ernie import Ernie, ErnieConfig

    model = Ernie(ErnieConfig(
        vocab_size=SZ.vocab, hidden_size=SZ.hidden, num_heads=SZ.heads,
        ffn_size=SZ.ffn, num_layers=SZ.layers, max_seq_len=SZ.seq))
    opt = optimizer.Adam(learning_rate=1e-4)
    step = make_train_step(model, opt, nn.functional.cross_entropy, amp=True)
    state = nn.get_state(model)
    ids = (_z(SZ.ernie_batch, SZ.seq, dtype=jnp.int32),)
    s = SingleDeviceSharding(v5e[0])
    compiled = step.lower(
        _shapes(state, s), _shapes(opt.init(state["params"]), s), _rng_key(s),
        _shapes(ids, s), _shapes(ids, s)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3 * SZ.layers
    assert compiled.memory_analysis().temp_size_in_bytes < 12 * 2**30


@pytest.mark.slow
def test_four_chip_step_compiles(v5e, as_tpu):
    from jax.sharding import Mesh

    from paddle_tpu.ps.sharded_cache import (
        make_sharded_ctr_train_step_from_keys)

    mesh = Mesh(np.asarray(v5e), ("ps",))
    model, opt, params = _deepfm_and_adam()
    step = make_sharded_ctr_train_step_from_keys(
        model, opt, chip_smoke._cache_cfg(SZ), mesh,
        slot_ids=np.arange(SZ.slots), axis="ps")
    cache, cmap = _ctr_state(2 * SZ.batch * SZ.slots)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("ps"))
    batch = (_z(SZ.batch, SZ.slots, dtype=jnp.uint32),
             _z(SZ.batch, SZ.dense), _z(SZ.batch, dtype=jnp.int32))
    hlo = step.lower(
        *_shapes((params, opt.init(params)), rep), _shapes(cache, row),
        _shapes(cmap, rep), *_shapes(batch, row)).compile().as_text()
    assert "all-to-all" in hlo   # K=4: auto routes


@pytest.mark.slow
def test_hybrid_step_compiles(v5e, as_tpu):
    """The dp×pp×cp×mp step of ``__graft_entry__.run_hybrid_step`` on
    the 2x2: flash attention under shard_map, ppermute, TP psums."""
    from __graft_entry__ import _factorize

    from paddle_tpu import optimizer
    from paddle_tpu.core import mesh as mesh_mod
    from paddle_tpu.models.ernie import ErnieConfig
    from paddle_tpu.parallel.hybrid import HybridParallelTrainer

    sizes = _factorize(len(v5e))
    pp, mp = sizes["pp"], sizes["mp"]
    mesh = mesh_mod.make_mesh(sizes, devices=v5e)
    cfg = ErnieConfig(vocab_size=64 * mp, hidden_size=8 * mp,
                      num_heads=2 * mp, ffn_size=16 * mp, num_layers=pp,
                      max_seq_len=64)
    tr = HybridParallelTrainer(cfg, mesh, optimizer.Adam(learning_rate=1e-3),
                               num_micro=2)
    ids = jax.ShapeDtypeStruct((2, 2 * sizes["dp"], 4 * sizes["cp"]),
                               jnp.int32)
    hlo = tr._step.lower(_shapes(tr.params), _shapes(tr.opt_state), ids, ids,
                         _rng_key()).compile().as_text()
    assert hlo.count("tpu_custom_call") == 3 * cfg.num_layers // pp


# ---------------------------------------------------------------------------
# the touched-rows push, as the chip compiles it (tier-1: a small tower)
# ---------------------------------------------------------------------------


RULES = ["naive", "adagrad", "std_adagrad", "adam"]


@pytest.mark.parametrize("rule", RULES)
def test_slab_step_on_the_touched_side_compiles(v5e, as_tpu, rule):
    """A slab step whose table dwarfs its batch (``auto`` → touched rows),
    for every rule (``naive``'s state columns are zero wide): no operand
    has the sweep's accumulator in it (leading dimension C+1), the
    scatters were told their indices are sorted and unique (XLA:TPU
    sorts them itself otherwise: the only sort left is the dedup's), and
    the rule is XLA's, not a kernel."""
    import re

    from paddle_tpu import optimizer
    from paddle_tpu.models.ctr import _packed_layout, make_ctr_train_step_slab
    from paddle_tpu.ps.embedding_cache import PUSH_CHUNK, resolve_push_mode

    sz = chip_smoke.Sizes(tower=(32, 32), batch=512, slab=2,
                          capacity=1 << 21)
    C, n = sz.capacity, sz.batch * sz.slots
    assert resolve_push_mode("auto", C, n) == "sparse"
    assert n > PUSH_CHUNK     # the rows are walked in chunks
    model = chip_smoke._deepfm(sz)
    opt = optimizer.Adam(learning_rate=1e-3)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    cfg = chip_smoke._cache_cfg(sz)
    cfg.embed_rule = cfg.embedx_rule = rule
    step = make_ctr_train_step_slab(
        model, opt, cfg, slot_ids=np.arange(sz.slots), batch_size=sz.batch,
        num_dense=sz.dense, slab=sz.slab, with_weights=True, amp=True)
    cache = _rows(C, sz.embedx_dim, rule)
    nb = 1 << 18
    cmap = _cmap(nb)
    total = _packed_layout(sz.batch, sz.slots, sz.dense, True)[3]
    hlo = _compile(step, SingleDeviceSharding(v5e[0]), params,
                   opt.init(params), cache, cmap,
                   _z(sz.slab, total, dtype=jnp.uint8)).as_text()
    assert f"[{C + 1}," not in hlo and f"[{C + 1}]" not in hlo
    sorts = re.findall(r"= \([^=]*\) sort\(.*?op_name=\"([^\"]*)\"", hlo)
    assert sorts and all("pt.push.accumulate" in s for s in sorts), sorts
    assert "tpu_custom_call" not in hlo


def test_sharded_hot_step_compiles(v5e, as_tpu):
    """The hot tier's mesh step on the 2x2: each chip probes its slice
    of the batch against the replicated banked map, the rows ride the
    all_to_all exchange, the owner pushes into its bank block."""
    from jax.sharding import Mesh

    from paddle_tpu import optimizer
    from paddle_tpu.ps.device_hash import DynamicDeviceKeyMap
    from paddle_tpu.ps.hot_tier import make_sharded_hot_train_step

    sz = chip_smoke.Sizes(tower=(32, 32), batch=512, capacity=1 << 21)
    mesh = Mesh(np.asarray(v5e), ("ps",))
    model = chip_smoke._deepfm(sz)
    opt = optimizer.Adam(learning_rate=1e-3)
    params = {"params": dict(model.named_parameters()), "buffers": {}}
    dmap = DynamicDeviceKeyMap(sz.capacity, banks=4)
    step = make_sharded_hot_train_step(
        model, opt, chip_smoke._cache_cfg(sz), mesh,
        slot_ids=np.arange(sz.slots), axis="ps",
        probe_buckets=dmap.probe_buckets, banks=dmap.banks)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P("ps"))
    batch = (_z(sz.batch, sz.slots, dtype=jnp.uint32),
             _z(sz.batch, sz.dense), _z(sz.batch, dtype=jnp.int32))
    hlo = step.lower(
        *_shapes((params, opt.init(params)), rep),
        _shapes(_rows(sz.capacity, sz.embedx_dim), row),
        _shapes(dmap.device_state(), rep), *_shapes(batch, row)
    ).compile().as_text()
    assert "all-to-all" in hlo and "tpu_custom_call" not in hlo

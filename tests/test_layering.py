"""Arrows point one way: ``ops/`` (kernels) <- ``nn/`` <- ``parallel/``
(layers over kernels) <- ``models/transformer.py`` (what the decoder models
share) <- ``models/<name>.py`` (a configuration and an assembly, nothing
another model imports). Read off the sources with ``ast``; and the state
each decoder model hands ``nn.get_state`` keeps the paths, in the order,
it had before the shared module existed (PR 50)."""

import ast
import hashlib
import pathlib

import pytest

PKG = pathlib.Path(__file__).resolve().parents[1] / "paddle_tpu"
DECODERS = ("olmoe", "joyai", "lfm2", "smallthinker", "evabyte")


def _imports(path):
    """Every module ``path`` imports, as an absolute dotted name (a
    ``from . import x`` names ``<package>.x``)."""
    package = ("paddle_tpu",) + path.relative_to(PKG).parts[:-1]
    found = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else ()
            module = ".".join(base + ((node.module,) if node.module else ()))
            found += [module] + [f"{module}.{a.name}" for a in node.names]
    return found


def test_no_model_imports_a_decoder_model():
    for path in sorted((PKG / "models").glob("*.py")):
        if path.name == "__init__.py":      # the package's own listing
            continue
        taken = [m for m in _imports(path) for d in DECODERS
                 if m.split(".")[:3] == ["paddle_tpu", "models", d]]
        assert not taken, (path.name, taken)


def test_kernels_and_nn_import_nothing_above_them():
    # ``ops/device_graph.py`` reads ``ps.device_hash`` today: a debt, and
    # the one import of ``ops/`` or ``nn/`` that leaves them upward
    assert "paddle_tpu.ps.device_hash" in _imports(
        PKG / "ops" / "device_graph.py")
    for layer in ("nn", "ops"):
        for path in sorted((PKG / layer).rglob("*.py")):
            above = [m for m in _imports(path)
                     if m.split(".")[:2] in (["paddle_tpu", "parallel"],
                                             ["paddle_tpu", "models"])]
            assert not above, (str(path.relative_to(PKG)), above)


def test_the_residual_path_is_an_op_under_one_shared_layer():
    """``ops/hyper_connection.py`` (PR 51; its four kernels PR 52) imports
    nothing of the package but ``core`` and is the one file of the path
    that names the kernel library; ``HyperConnected`` sits in the shared
    module and calls the fused entry points, and the one model that asks
    for it (``models/joyai.py`` as Xing4.0 runs it) takes it from there."""
    ops = _imports(PKG / "ops" / "hyper_connection.py")
    assert [m for m in ops if m.startswith("paddle_tpu.")
            and not m.startswith("paddle_tpu.core")] == []
    assert "pallas" not in (PKG / "models" / "transformer.py").read_text()
    shared = _imports(PKG / "models" / "transformer.py")
    for name in ("hc_pre", "hc_gates", "hc_post", "hc_res_err"):
        assert f"paddle_tpu.ops.hyper_connection.{name}" in shared, name
    joyai = _imports(PKG / "models" / "joyai.py")
    for name in ("HyperConnected", "yarn_mscale", "rotary_pairs"):
        assert f"paddle_tpu.models.transformer.{name}" in joyai, name
    assert not [m for m in joyai if "hyper_connection" in m]


def test_the_expert_layers_file_names_no_kernel_library():
    assert (PKG / "ops" / "grouped_matmul.py").exists()
    assert "pallas" not in (PKG / "parallel" / "moe.py").read_text()


def _olmoe():
    from paddle_tpu.models.olmoe import Olmoe, OlmoeConfig
    return Olmoe(OlmoeConfig(
        vocab_size=1024, hidden_size=256, num_heads=2, num_layers=2,
        num_experts=8, experts_per_token=2, expert_size=128,
        max_seq_len=512))


def _joyai():
    from paddle_tpu.models.joyai import Joyai, JoyaiConfig
    return Joyai(JoyaiConfig(
        vocab_size=1024, hidden_size=256, num_heads=2, num_layers=2,
        dense_size=512, q_rank=192, kv_rank=128, num_experts=16,
        experts_per_token=4, expert_size=768, held=(4, 2), max_seq_len=512))


def _xing4():
    from paddle_tpu.models.joyai import Joyai, JoyaiConfig
    return Joyai(JoyaiConfig(
        vocab_size=1024, hidden_size=256, num_heads=2, num_layers=2,
        dense_size=512, q_rank=192, kv_rank=128, num_experts=16,
        experts_per_token=4, expert_size=768, held=(4, 2), max_seq_len=512,
        num_mtp=0, hc_mult=4, recompute="blocks"))


def _lfm2():
    from paddle_tpu.models.lfm2 import Lfm2, Lfm2Config
    return Lfm2(Lfm2Config(
        vocab_size=1024, hidden_size=256, num_heads=4, num_kv_heads=1,
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        dense_size=512, num_experts=8, experts_per_token=4, expert_size=256,
        held=(4, 2), max_seq_len=512))


def _smallthinker():
    from paddle_tpu.models.smallthinker import (SmallThinker,
                                                SmallThinkerConfig)
    return SmallThinker(SmallThinkerConfig(
        vocab_size=1024, hidden_size=256, num_heads=14, num_kv_heads=2,
        head_dim=128, sliding_window_size=512, num_layers=2,
        router_width=16, experts_per_token=4, expert_size=768, held=(4, 2),
        max_seq_len=2048, recompute="experts"))


def _evabyte():
    from paddle_tpu.models.evabyte import EvaByte, EvaByteConfig
    return EvaByte(EvaByteConfig(
        hidden_size=256, num_heads=2, intermediate_size=512, num_layers=2,
        num_pred_heads=8, recompute="blocks"))


@pytest.mark.parametrize("build,entries,want", [
    (_olmoe, 30, "21f8b10d5da913af"),
    (_joyai, 58, "b8ba875b76bf57d5"),
    (_xing4, 50, "2579d1cf01f7b150"),
    (_lfm2, 38, "8698a32a62ccaaef"),
    (_smallthinker, 28, "fd35acd5aba9377d"),
    (_evabyte, 25, "62705c4137a4ae9b")])
def test_decoder_state_keeps_the_parents_paths(build, entries, want):
    """Each decoder model at ``tests/test_tpu_lowering.py``'s small widths:
    the parameter paths, then the buffer paths, of ``nn.get_state``, with
    their shapes, in order — counted and hashed on PR 49's tree (``git
    archive``) before anything moved (``_xing4``: the same file over four
    residual streams without a prediction module, taken on PR 51's tree,
    so that what it ADDED — ``blocks.N.hc_attn.*``, ``blocks.N.hc_ffn.*``,
    ``hc_res_err`` — keeps its place too). A renamed, reordered or reshaped
    entry is a checkpoint that no longer loads and a benchmark adapter
    that no longer finds ``blocks.N.moe.router_w``."""
    from paddle_tpu import nn

    state = nn.get_state(build())
    lines = [f"{kind} {path} {tuple(value.shape)}"
             for kind in ("params", "buffers")
             for path, value in state[kind].items()]
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]
    assert (len(lines), digest) == (entries, want), "\n".join(lines)

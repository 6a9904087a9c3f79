"""chip_smoke.py off the chip: the script refuses a non-TPU platform by
name, its legs — imported as functions — pass on CPU at tiny sizes, and
the compile-cache helper keeps the cache where the contract says."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    slots=4, dense=3, embedx_dim=4, tower=(16, 16), batch=64,
    capacity=1 << 12, ids_per_slot=200, pass_batches=8, slab=4,
    stream_batches=4, vocab=64, hidden=32, heads=2, ffn=64, layers=2,
    seq=16, ernie_batch=2, ernie_steps=3, window_heads=(4, 2, 8),
    window_seq=48, window=16, eva_heads=(2, 8), eva_seq=64, eva_window=16,
    eva_chunk=4, hc_streams=4, hc_hidden=32, hc_seq=24)


def test_script_refuses_a_cpu_platform_by_name():
    """Under JAX_PLATFORMS=cpu the script must exit non-zero, say which
    platform it found, and print no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode != 0, out.stdout
    assert "platform: cpu" in out.stdout
    assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_leg_pass_tiny():
    facts = chip_smoke.leg_pass(TINY)
    assert facts["loss"][1] < facts["loss"][0]
    assert facts["push_mode"] == "sparse"   # what auto resolves to off-TPU
    # ... with the shapes it was resolved for, one entry a compiled shape
    assert {"capacity": TINY.capacity, "rows": TINY.batch * TINY.slots,
            "mode": "sparse"} in facts["push_select"]


def test_leg_stream_tiny():
    facts = chip_smoke.leg_stream(TINY)
    assert facts["warm_rpcs"] == {}
    assert facts["push_mode"] == "sparse" and all(
        f["capacity"] == TINY.capacity for f in facts["push_select"])


def test_leg_dense_tiny():
    facts = chip_smoke.leg_dense(TINY)
    assert facts["attn_impl"] == "einsum" and facts["mosaic_calls"] == 0
    assert set(facts["flash_rel_err"]) == {
        "highest", "default", "window_highest", "window_default",
        "eva_highest", "eva_default"}
    assert facts["hc_rel_err"] <= 5e-4 and 0 < facts["hc_res_err"] <= 1e-4


def test_leg_four_tiny():
    facts = chip_smoke.leg_four(TINY, jax.devices()[:4])
    assert facts["overflow"] == 0 and facts["shard_devices"] == 4
    shard = {f["capacity"] for f in facts["push_select"]}
    assert shard == {TINY.capacity, TINY.capacity // 4}, facts["push_select"]


def test_compile_cache_path_is_fixed_or_the_environments(monkeypatch, tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is left alone — jax reads it and no
    code sets another path; otherwise the path is <checkout>/.jax_cache
    from any working directory, and it is exported for child processes."""
    from paddle_tpu.core.compile_cache import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert enable_compile_cache() == "/x" and updates == []

    default = os.path.join(REPO, ".jax_cache")
    for cwd in (REPO, str(tmp_path)):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir(cwd)
        assert enable_compile_cache() == default
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == default
    assert updates == [("jax_compilation_cache_dir", default)] * 2

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import flags, mesh
from paddle_tpu.core import enforce as _unused  # noqa: F401
from paddle_tpu.core import enforce_module as enforce


def test_flags_define_get_set():
    flags.define_flag("test_only_flag", 3, "test")
    assert pt.get_flags("test_only_flag")["test_only_flag"] == 3
    pt.set_flags({"test_only_flag": 7})
    assert pt.get_flags(["test_only_flag"])["test_only_flag"] == 7
    with pytest.raises(KeyError):
        pt.set_flags({"nonexistent_flag_xyz": 1})


def test_flags_type_coercion():
    flags.define_flag("test_bool_flag", False)
    pt.set_flags({"test_bool_flag": "true"})
    assert pt.get_flags("test_bool_flag")["test_bool_flag"] is True


def test_enforce_helpers():
    enforce.enforce_eq(1, 1)
    with pytest.raises(enforce.InvalidArgumentError):
        enforce.enforce_eq(1, 2)
    with pytest.raises(enforce.PreconditionNotMetError):
        enforce.enforce(False, "nope")
    assert enforce.enforce_not_none(5) == 5


def test_places():
    p = pt.CPUPlace()
    assert p.jax_device().platform == "cpu"
    assert pt.core.device_count("cpu") == 8  # virtual devices from conftest
    with pytest.raises(enforce.InvalidArgumentError):
        pt.core.CUDAPlace(0)


def test_mesh_construction():
    m = mesh.make_mesh({"dp": 2, "mp": 4})
    assert m.shape == {"dp": 2, "mp": 4}
    with pytest.raises(enforce.InvalidArgumentError):
        mesh.make_mesh({"dp": 3})
    hm = mesh.make_hybrid_mesh(dp=2, mp=4)
    assert hm.shape["dp"] == 2 and hm.shape["mp"] == 4 and hm.shape["pp"] == 1


def test_use_mesh_context():
    m = mesh.make_mesh({"dp": 8})
    assert mesh.current_mesh() is None
    with mesh.use_mesh(m):
        assert mesh.current_mesh() is m
    assert mesh.current_mesh() is None


def test_nan_inf_checker():
    from paddle_tpu.core.nan_inf import check_numerics, count_nonfinite

    good = {"a": np.ones(4, np.float32)}
    check_numerics(good)
    bad = {"a": np.array([1.0, np.nan], np.float32)}
    with pytest.raises(enforce.PreconditionNotMetError):
        check_numerics(bad)
    assert int(count_nonfinite(bad)) == 1
    assert int(count_nonfinite(good)) == 0


def test_profiler_host_events():
    from paddle_tpu.core import profiler

    profiler.reset_host_events()
    with profiler.RecordEvent("unit_scope"):
        pass
    stats = profiler.host_event_stats()
    assert stats["unit_scope"]["count"] == 1


def test_profiler_timed_waits_for_the_device(monkeypatch):
    """core.profiler.timed: a warm-up call outside the clock, ``iters``
    dispatches inside it, closed by block_until_ready on the LAST output
    (the one sync primitive — measured equal to a D2H fetch on the
    chip, see its docstring)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core import profiler

    calls, synced = [], []
    real_block = jax.block_until_ready

    def fn(x):
        calls.append(1)
        return x + len(calls)

    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: synced.append(x) or real_block(x))
    t, out = profiler.timed(fn, jnp.zeros((64,)), iters=3)
    assert t > 0 and len(calls) == 1 + 3
    assert float(out[0]) == 4.0            # the last dispatch's output
    assert len(synced) == 2 and synced[-1] is out   # warm-up, then the close


def test_every_flag_is_read():
    """A flag is an option: one that nothing reads configures nothing.
    Every ``define_flag`` name under ``paddle_tpu/``, ``tools/`` and
    ``chip_smoke.py`` is read or set somewhere outside its definition —
    as an argument of ``flag(...)``, ``get_flags(...)`` or
    ``set_flags(...)``, or spelled ``FLAGS_<name>`` (the environment
    bootstrap and the docs' spelling), or handed on by name as a
    ``*_flag="<name>"`` argument (``ps/rpc.py`` picks its deadline and
    retry flags by QoS class that way)."""
    import ast
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = [os.path.join(root, "chip_smoke.py")]
    for top in ("paddle_tpu", "tools"):
        for d, _, names in os.walk(os.path.join(root, top)):
            files += [os.path.join(d, n) for n in names if n.endswith(".py")]

    def called(node):
        f = node.func
        return f.id if isinstance(f, ast.Name) else getattr(f, "attr", None)

    defined, used = {}, set()
    for path in files:
        with open(path) as f:
            src = f.read()
        lines = src.splitlines()
        for node in ast.walk(ast.parse(src)):
            if not isinstance(node, ast.Call):
                continue
            if called(node) == "define_flag" and node.args and isinstance(
                    node.args[0], ast.Constant):
                defined[node.args[0].value] = os.path.relpath(path, root)
                # a definition's own text (its help string) is not a use
                for i in range(node.lineno - 1, node.end_lineno):
                    lines[i] = ""
            elif called(node) in ("flag", "get_flags", "set_flags"):
                used |= {c.value for a in node.args + node.keywords
                         for c in ast.walk(a) if isinstance(c, ast.Constant)
                         and isinstance(c.value, str)}
        rest = "\n".join(lines)
        used |= set(re.findall(r"FLAGS_(\w+)", rest))
        used |= set(re.findall(r'\w+_flag(?:: str)? ?= ?"(\w+)"', rest))
    assert len(defined) > 20, defined
    unread = {n: p for n, p in defined.items() if n not in used}
    assert not unread, f"defined and read by nothing: {unread}"

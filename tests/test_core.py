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

"""Aux subsystems: hapi Model.fit, auto-checkpoint resume, elastic
manager decisions, local launcher (SURVEY §5 + §2.1 L14)."""

import os
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed.elastic import (ElasticManager, ElasticStatus,
                                            FileStore, MemoryStore)
from paddle_tpu.distributed.launch import JobSpec, launch_local
from paddle_tpu.hapi import Model
from paddle_tpu.io.auto_checkpoint import CheckpointSaver, TrainEpochRange


# -- hapi -------------------------------------------------------------------


def _toy_data(n=64, batch=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    w = rng.normal(size=(8,)).astype(np.float32)
    y = (x @ w > 0).astype(np.int32)
    return [(x[i:i + batch], y[i:i + batch]) for i in range(0, n, batch)]


def test_model_fit_learns(tmp_path):
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    model = Model(net)
    model.prepare(optimizer.Adam(learning_rate=1e-2), nn.CrossEntropyLoss())
    data = _toy_data()
    hist = model.fit(data, epochs=5, verbose=0)
    assert hist["loss"][-1] < hist["loss"][0]

    model.save(str(tmp_path / "m"))
    model2 = Model(net)
    model2.prepare(optimizer.Adam(learning_rate=1e-2), nn.CrossEntropyLoss())
    model2.load(str(tmp_path / "m"))
    x, y = data[0]
    out = model2.predict_batch(x)
    assert out.shape == (16, 2)
    ev = model2.evaluate(data)
    assert ev["eval_loss"] == pytest.approx(hist["loss"][-1], rel=0.5)


# -- auto checkpoint --------------------------------------------------------


def test_checkpoint_saver_gc(tmp_path):
    s = CheckpointSaver(str(tmp_path), max_keep=2)
    for i in range(4):
        s.save({"v": i}, {"epoch": i})
    no, payload, meta = s.get_last()
    assert no == 3 and payload["v"] == 3 and meta["epoch"] == 3
    assert s._ids() == [2, 3]  # older snapshots GC'd


def test_train_epoch_range_resumes(tmp_path):
    state = {"w": 0.0}

    def run(crash_after=None):
        seen = []
        r = TrainEpochRange(5, "job", checkpoint_dir=str(tmp_path))
        r.set_state_getter(lambda: dict(state))
        r.set_state_setter(lambda s: state.update(s))
        for epoch in r:
            state["w"] += 1.0
            seen.append(epoch)
            if crash_after is not None and epoch == crash_after:
                r.save(epoch)
                raise RuntimeError("simulated crash")
        return seen

    with pytest.raises(RuntimeError):
        run(crash_after=2)
    assert state["w"] == 3.0
    state["w"] = -100.0  # clobber; resume must restore from snapshot
    seen = run()
    assert seen == [3, 4]          # epochs 0-2 skipped
    assert state["w"] == 5.0       # restored 3.0 + two more epochs


def test_train_epoch_range_resumes_mid_epoch_steps(tmp_path):
    """A MID-epoch snapshot (save(epoch, step)) must re-enter ITS epoch
    and skip exactly the completed steps — not restart the epoch from
    scratch (the pre-fix behavior re-trained them) and not skip to the
    next epoch (which would silently drop the unfinished tail)."""
    state = {"w": 0.0}
    steps_per_epoch = 4

    def run(crash_at=None):
        trained = []  # (epoch, step) actually trained this run
        r = TrainEpochRange(2, "midjob", checkpoint_dir=str(tmp_path))
        r.set_state_getter(lambda: dict(state))
        r.set_state_setter(lambda s: state.update(s))
        for epoch in r:
            for step, _ in r.steps(range(steps_per_epoch)):
                state["w"] += 1.0
                trained.append((epoch, step))
                if crash_at is not None and (epoch, step) == crash_at:
                    r.save(epoch, step=step + 1)  # steps 0..step done
                    raise RuntimeError("simulated crash")
        return trained

    with pytest.raises(RuntimeError):
        run(crash_at=(1, 1))
    assert state["w"] == 6.0  # epoch 0 (4 steps) + epoch-1 steps 0-1
    state["w"] = -100.0
    trained = run()
    # resume re-enters epoch 1 at step 2: no step replayed, none dropped
    assert trained == [(1, 2), (1, 3)]
    assert state["w"] == 8.0


def test_train_epoch_range_mid_epoch_resume_requires_cursor(tmp_path):
    """A mid-epoch resume whose caller runs a PLAIN inner loop (neither
    r.steps() nor a step_in_epoch read) silently re-trains the
    completed steps — the range must fail loudly at that epoch's end
    instead of corrupting the restored weights."""
    state = {"w": 0.0}
    r = TrainEpochRange(3, "midguard", checkpoint_dir=str(tmp_path))
    r.set_state_getter(lambda: dict(state))
    r.set_state_setter(lambda s: state.update(s))
    r.save(0, step=2)   # mid-epoch snapshot of epoch 0, then "crash"

    r2 = TrainEpochRange(3, "midguard", checkpoint_dir=str(tmp_path))
    r2.set_state_getter(lambda: dict(state))
    r2.set_state_setter(lambda s: state.update(s))
    with pytest.raises(Exception, match="never skipped"):
        for epoch in r2:
            pass   # plain loop: cursor never consumed

    # consuming the cursor (reading step_in_epoch) satisfies the guard
    r3 = TrainEpochRange(3, "midguard", checkpoint_dir=str(tmp_path))
    r3.set_state_getter(lambda: dict(state))
    r3.set_state_setter(lambda s: state.update(s))
    seen = []
    for epoch in r3:
        seen.append((epoch, r3.step_in_epoch))
    assert seen[0] == (0, 2) and [e for e, _ in seen] == [0, 1, 2]


def test_train_epoch_range_cursor_consumed_before_loop(tmp_path):
    """Reading step_in_epoch BEFORE the epoch loop (the documented
    consume-before-the-loop pattern: the caller skips the completed
    steps themselves) must satisfy the skip guard — __iter__ must not
    re-arm it and kill the correct resume at the epoch's end."""
    state = {"w": 0.0}
    r = TrainEpochRange(2, "preloop", checkpoint_dir=str(tmp_path))
    r.set_state_getter(lambda: dict(state))
    r.set_state_setter(lambda s: state.update(s))
    r.save(0, step=2)   # mid-epoch snapshot of epoch 0, then "crash"

    r2 = TrainEpochRange(2, "preloop", checkpoint_dir=str(tmp_path))
    r2.set_state_getter(lambda: dict(state))
    r2.set_state_setter(lambda s: state.update(s))
    assert r2.step_in_epoch == 2   # consumed before the loop starts
    seen = [epoch for epoch in r2]   # must NOT raise "never skipped"
    assert seen == [0, 1]


# -- elastic ----------------------------------------------------------------


def _mk_managers(store, n, np_=None, **kw):
    return [ElasticManager(store, "job", np_ or n, f"host{i}",
                           heartbeat_interval=0.05, heartbeat_ttl=0.3,
                           elastic_timeout=0.3, **kw)
            for i in range(n)]


def test_elastic_healthy_holds():
    store = MemoryStore()
    ms = _mk_managers(store, 2)
    for m in ms:
        m.start()
    try:
        assert ms[0].watch_once() == ElasticStatus.HOLD
        assert ms[0]._match()
    finally:
        for m in ms:
            m.stop()


def test_elastic_node_death_restarts():
    import time
    store = MemoryStore()
    ms = _mk_managers(store, 3, min_np=2, max_np=3)
    for m in ms:
        m.start()
    ms[2].stop()                      # node dies
    time.sleep(0.4)                   # ttl expiry + timeout
    st = ms[0].watch_once()
    time.sleep(0.4)
    st = ms[0].watch_once()
    assert st == ElasticStatus.RESTART
    assert ms[0].adopt_world() == 2   # shrunk world
    for m in ms[:2]:
        m.stop()


def test_elastic_below_min_errors():
    import time
    store = MemoryStore()
    ms = _mk_managers(store, 2, min_np=2, max_np=3)
    ms[0].start()
    ms[1].start()
    ms[1].stop()
    time.sleep(0.4)
    ms[0].watch_once()
    time.sleep(0.4)
    assert ms[0].watch_once() == ElasticStatus.ERROR
    ms[0].stop()


def test_file_store_roundtrip(tmp_path):
    s = FileStore(str(tmp_path))
    s.put("elastic/j/nodes/h0", "x", ttl=100)
    assert s.get("elastic/j/nodes/h0") == "x"
    assert list(s.list_prefix("elastic/j/nodes/")) == ["elastic/j/nodes/h0"]
    s.delete("elastic/j/nodes/h0")
    assert s.get("elastic/j/nodes/h0") is None


def test_tcp_elastic_store_roundtrip_and_lease_expiry():
    """TcpElasticStore (VERDICT r4 #6): the etcd-lease role over the
    cluster TCPStore — master + a second client process-equivalent,
    TTL expiry on read, prefix scans, and the ElasticManager's
    heartbeat/membership loop running over it."""
    import time

    from paddle_tpu.distributed.elastic import (ElasticManager,
                                                TcpElasticStore,
                                                store_from_spec)

    master = TcpElasticStore(is_master=True)
    try:
        client = store_from_spec(f"tcp:127.0.0.1:{master.port}")
        client.put("elastic/j/nodes/h0", "x", ttl=100)
        client.put("elastic/j/nodes/h1", "y", ttl=0.3)
        client.put("other/k", "z")
        # both sides observe the same keys (it IS one store)
        assert master.get("elastic/j/nodes/h0") == "x"
        assert sorted(master.list_prefix("elastic/j/nodes/")) == [
            "elastic/j/nodes/h0", "elastic/j/nodes/h1"]
        time.sleep(0.35)  # h1's lease expires without any sweeper
        assert master.get("elastic/j/nodes/h1") is None
        assert list(master.list_prefix("elastic/j/nodes/")) == [
            "elastic/j/nodes/h0"]
        client.delete("elastic/j/nodes/h0")
        assert master.get("elastic/j/nodes/h0") is None

        # the manager's full heartbeat/membership loop over this store
        ms = _mk_managers(master, 2)
        for m in ms:
            m.start()
        try:
            assert ms[0].watch_once() == ElasticStatus.HOLD
            assert ms[0]._match()
        finally:
            for m in ms:
                m.stop()
        client.close()
    finally:
        master.close()


# -- launcher ---------------------------------------------------------------


def test_launch_local_trainers(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        rank = os.environ["PADDLE_TRAINER_ID"]
        n = os.environ["PADDLE_TRAINERS_NUM"]
        assert os.environ["TRAINING_ROLE"] == "TRAINER"
        print(f"rank {rank}/{n} ok")
        sys.exit(0)
    """))
    rc = launch_local(JobSpec([str(script)], nproc=2,
                              log_dir=str(tmp_path / "logs")), timeout=60)
    assert rc == 0
    logs = sorted(os.listdir(tmp_path / "logs"))
    assert logs == ["trainer_0.log", "trainer_1.log"]
    assert "rank 0/2 ok" in (tmp_path / "logs" / "trainer_0.log").read_text()


def test_launch_propagates_failure(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(3)")
    rc = launch_local(JobSpec([str(script)], nproc=2), timeout=60)
    assert rc == 3


def test_trainer_dump_fields(tmp_path):
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer
    from paddle_tpu.executor import Trainer

    pt.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    tr = Trainer(model, optimizer.SGD(0.1), nn.functional.cross_entropy)
    tr.set_dump_config(str(tmp_path), fields=("loss", "input:0", "label:0"),
                       trainer_id=3)
    rng = np.random.default_rng(0)
    for _ in range(3):
        tr.train_step(rng.normal(size=(8, 4)).astype(np.float32),
                      rng.integers(0, 2, 8))
    tr.set_dump_config(None)  # close
    lines = (tmp_path / "trainer-003.dump").read_text().strip().splitlines()
    assert len(lines) == 9  # 3 steps x 3 fields
    assert lines[0].split("\t")[1] == "loss"
    steps = {int(l.split("\t")[0]) for l in lines}
    assert steps == {1, 2, 3}


def test_print_table_stat():
    import numpy as np

    from paddle_tpu.ps.table import MemorySparseTable, TableConfig

    t = MemorySparseTable(TableConfig(shard_num=4))
    t.pull_sparse(np.arange(1, 101, dtype=np.uint64))
    msg = t.print_table_stat()
    assert "100 features" in msg and "4 shards" in msg
    assert int(t.shard_sizes().sum()) == 100


def test_ps_op_cost_profiling():
    """PS ops feed the CostProfiler aggregator under the reference's
    scope names (cost_timer.h probes: pserver_sparse_select_all in
    MemorySparseTable::PullSparse, memory_sparse_table.cc:419)."""
    import numpy as np

    from paddle_tpu.core.profiler import host_event_stats, reset_host_events
    from paddle_tpu.ps.table import MemorySparseTable, TableConfig

    reset_host_events()
    t = MemorySparseTable(TableConfig(shard_num=2))
    keys = np.arange(1, 100, dtype=np.uint64)
    t.pull_sparse(keys)
    push = np.zeros((99, t.accessor.push_dim), np.float32)
    push[:, 1] = 1.0
    t.push_sparse(keys, push)
    st = host_event_stats()
    assert st["pserver_sparse_select_all"]["count"] == 1
    assert st["pserver_sparse_update_all"]["count"] == 1
    assert st["pserver_sparse_update_all"]["avg_s"] > 0


def test_timeline_merges_worker_traces(tmp_path):
    """tools/timeline.py: per-worker chrome traces merge into one file
    with a named pid lane per worker (the reference timeline tool)."""
    import json
    import sys

    sys.path.insert(0, str(
        __import__("pathlib").Path(__file__).resolve().parents[1] / "tools"))
    from timeline import merge_traces

    from paddle_tpu.core.profiler import (RecordEvent, export_chrome_tracing,
                                          start_timeline)

    files = []
    for w in range(2):
        start_timeline()
        with RecordEvent(f"work_{w}"):
            pass
        p = tmp_path / f"worker{w}.json"
        export_chrome_tracing(str(p))
        files.append(str(p))

    out = tmp_path / "merged.json"
    n = merge_traces(files, str(out))
    blob = json.loads(out.read_text())
    evs = blob["traceEvents"]
    assert n == len(evs)
    lanes = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
    assert lanes == {"worker0", "worker1"}
    pids = {e["pid"] for e in evs if e.get("ph") == "X"}
    assert pids == {0, 1}
    names = {e["name"] for e in evs if e.get("ph") == "X"}
    assert {"work_0", "work_1"} <= names


def test_hapi_prepare_amp_configs(rng):
    """Model.prepare(amp_configs=...) — the reference hapi's mixed-
    precision knob: 'O1'/'O2'/True/dict enable bf16 contractions in the
    step; None/'O0' keep f32."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import hapi, nn, optimizer

    pt.seed(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = rng.integers(0, 2, 32).astype(np.int32)
    for amp_cfg, expect_bf16 in ((None, False), ("O0", False),
                                 ("O1", True), ({"level": "O2"}, True),
                                 ({"init_loss_scaling": 1024.0}, True)):
        m = hapi.Model(nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                     nn.Linear(16, 2)))
        m.prepare(optimizer.Adam(1e-2), nn.functional.cross_entropy,
                  amp_configs=amp_cfg)
        out = m.train_batch(x, y)
        assert np.isfinite(out["loss"])
        txt = m._train_step.lower(
            m._state, m._opt_state, jax.random.key(0),
            (jnp.asarray(x),), (jnp.asarray(y),)).as_text()
        assert ("bf16" in txt) == expect_bf16, (amp_cfg, expect_bf16)
    # the reference rejects unknown levels; so do we
    m = hapi.Model(nn.Linear(8, 2))
    with pytest.raises(Exception, match="O0/O1/O2"):
        m.prepare(optimizer.Adam(1e-2), nn.functional.cross_entropy,
                  amp_configs="o1")


def test_hapi_o2_master_weights(rng):
    """amp_configs='O2' — pure bf16 parameter storage with f32 master
    weights (paddle.amp.decorate(level='O2') + multi_precision
    optimizer semantics): params live in bf16, masters carry full
    precision, the model trains, and the bf16 params stay exact
    projections of the masters every step."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import hapi, nn, optimizer
    from paddle_tpu.optimizer import MasterWeights

    pt.seed(0)
    x = rng.normal(size=(64, 8)).astype(np.float32)
    y = (x @ rng.normal(size=(8, 2)).astype(np.float32)).argmax(-1).astype(
        np.int32)
    m = hapi.Model(nn.Sequential(nn.Linear(8, 32), nn.ReLU(),
                                 nn.Linear(32, 2)))
    m.prepare(optimizer.Adam(5e-3), nn.functional.cross_entropy,
              amp_configs="O2")
    assert isinstance(m._opt, MasterWeights)
    for p in m._state["params"].values():
        assert p.dtype == jnp.bfloat16, p.dtype
    masters = m._opt_state["slots"]["master"]
    for k, mm in masters.items():
        assert mm.dtype == jnp.float32, k
    losses = [m.train_batch(x, y)["loss"] for _ in range(30)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    # params are pure projections of the masters (no drift channel)
    masters = m._opt_state["slots"]["master"]
    for k, p in m._state["params"].items():
        np.testing.assert_array_equal(
            np.asarray(p), np.asarray(masters[k].astype(jnp.bfloat16)), k)


def test_checkpoint_structured_array_roundtrip(tmp_path):
    """Advisor r4 (low): a genuine structured/record array is also
    numpy kind 'V' but is NOT an ml_dtypes scalar — it must take the
    plain savez path and round-trip, not fail at the uint-view."""
    from paddle_tpu.io import checkpoint as ckpt

    rec = np.array([(1, 2.5), (3, 4.5)],
                   dtype=[("k", np.int64), ("v", np.float32)])
    ckpt.save({"rec": rec}, str(tmp_path / "rec"))
    back = ckpt.load(str(tmp_path / "rec"))
    assert back["rec"].dtype == rec.dtype
    np.testing.assert_array_equal(back["rec"], rec)


def test_hapi_o2_checkpoint_roundtrip(rng, tmp_path):
    """O2 bf16 params survive save/load bit-exactly (np.savez degrades
    ml_dtypes arrays to raw void without the serializer's dtype-tagged
    integer view)."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import hapi, nn, optimizer
    from paddle_tpu.io import checkpoint as ckpt

    # serializer-level: bf16 round-trips with dtype intact
    arr = {"w": jnp.asarray(rng.normal(size=(4, 3)), jnp.bfloat16)}
    ckpt.save(arr, str(tmp_path / "bf16"))
    back = ckpt.load(str(tmp_path / "bf16"))
    assert back["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(back["w"]).view(np.uint16),
                                  np.asarray(arr["w"]).view(np.uint16))

    # model-level: O2 save -> load -> training continues
    pt.seed(0)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.integers(0, 2, 16).astype(np.int32)
    m = hapi.Model(nn.Linear(8, 2))
    m.prepare(optimizer.Adam(1e-2), nn.functional.cross_entropy,
              amp_configs="O2")
    m.train_batch(x, y)
    m.save(str(tmp_path / "o2"))
    m2 = hapi.Model(nn.Linear(8, 2))
    m2.prepare(optimizer.Adam(1e-2), nn.functional.cross_entropy,
               amp_configs="O2")
    m2.load(str(tmp_path / "o2"))
    for k, v in m2._state["params"].items():
        assert np.asarray(v).dtype == jnp.bfloat16, k
    assert np.isfinite(m2.train_batch(x, y)["loss"])


def test_master_weights_rejects_meta_optimizer():
    """Wrapping order is enforced: MasterWeights(plain) only; a meta
    wrapper inside would half-apply loss scaling."""
    from paddle_tpu import optimizer
    from paddle_tpu.core.enforce import EnforceNotMet
    from paddle_tpu.distributed.meta_optimizers import AMPOptimizer

    with pytest.raises(EnforceNotMet, match="MasterWeights"):
        optimizer.MasterWeights(AMPOptimizer(optimizer.Adam(1e-3)))


def test_amp_optimizer_composes_outside_master_weights(rng):
    """The DOCUMENTED composition — AMPOptimizer(MasterWeights(plain))
    — actually trains: dynamic loss scaling outside, f32 masters
    inside, bf16 params throughout."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import optimizer
    from paddle_tpu.distributed.meta_optimizers import AMPOptimizer

    p32 = {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)}
    opt = AMPOptimizer(optimizer.MasterWeights(optimizer.Adam(1e-2)))
    state = opt.init(p32)
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), p32)
    g = {"w": jnp.full((8, 4), 0.01, jnp.float32)}
    for _ in range(5):
        # grads of the SCALED loss, as the step factory produces them
        sg = jax.tree.map(
            lambda x: x * state["scaler"].loss_scale, g)
        params, state = opt.update(sg, state, params)
    assert params["w"].dtype == jnp.bfloat16
    masters = state["inner"]["slots"]["master"]["w"]
    assert masters.dtype == jnp.float32
    assert np.isfinite(np.asarray(masters)).all()
    # the params moved (updates were not skipped / zeroed by scaling)
    assert not np.array_equal(
        np.asarray(params["w"]).view(np.uint16),
        np.asarray(p32["w"].astype(jnp.bfloat16)).view(np.uint16))


def test_master_weights_matches_f32_trajectory(rng):
    """MasterWeights(Adam) fed the SAME f32 grads reproduces plain f32
    Adam's master trajectory exactly (the wrapper adds no math), while
    exposing bf16 params."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import optimizer

    p32 = {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)}
    p16 = jax.tree.map(lambda p: p.astype(jnp.bfloat16), p32)
    g = {"w": jnp.asarray(rng.normal(size=(8, 4)) * 0.01, jnp.float32)}
    ref, o2 = optimizer.Adam(1e-2), optimizer.MasterWeights(
        optimizer.Adam(1e-2))
    rs, os_ = ref.init(p32), o2.init(p32)  # masters seeded from f32
    for _ in range(10):
        p32, rs = ref.update(g, rs, p32)
        p16, os_ = o2.update(g, os_, p16)
    np.testing.assert_array_equal(
        np.asarray(os_["slots"]["master"]["w"]), np.asarray(p32["w"]))
    assert p16["w"].dtype == jnp.bfloat16


def test_decorate_o2_composes_with_meta_wrappers(rng):
    """decorate_o2 inserts MasterWeights around the INNERMOST plain
    optimizer: AMPOptimizer(Adam) becomes AMPOptimizer(MasterWeights(
    Adam)); already-decorated chains are left alone (review finding:
    the naive isinstance check dead-ended the documented composition)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import optimizer
    from paddle_tpu.distributed.meta_optimizers import AMPOptimizer
    from paddle_tpu.optimizer import MasterWeights, decorate_o2

    p32 = {"w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)}

    # meta wrapper outside: MasterWeights inserted inside
    opt, state, p16 = decorate_o2(AMPOptimizer(optimizer.Adam(1e-2)), p32)
    assert isinstance(opt, AMPOptimizer)
    assert isinstance(opt.inner, MasterWeights)
    assert p16["w"].dtype == jnp.bfloat16
    g = jax.tree.map(lambda x: x * state["scaler"].loss_scale,
                     {"w": jnp.full((4, 4), 0.01, jnp.float32)})
    p16, state = opt.update(g, state, p16)
    assert p16["w"].dtype == jnp.bfloat16

    # already decorated: unchanged, not double-wrapped
    pre = AMPOptimizer(MasterWeights(optimizer.Adam(1e-2)))
    opt2, _, _ = decorate_o2(pre, p32)
    assert opt2 is pre and isinstance(opt2.inner, MasterWeights)
    assert not isinstance(opt2.inner.inner, MasterWeights)

"""JAX's compile pipeline as the program's own spans and counters
(``core/profiler``'s ``jax.monitoring`` listener): ``pt.compile.trace``,
``pt.compile.lower``, ``pt.compile`` under the ``RecordEvent`` that caused
them, the three ``pt_compile_*`` counters, ``pt.native.build``; and the
benchmark's ``setup_*`` readers (``benchmarks/harness/setup_spans.py``) on
fabricated rings."""

import os
import sys
import threading
import types

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.core import profiler
from paddle_tpu.core.profiler import HostSpan, RecordEvent, host_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

from harness import setup_spans, spec  # noqa: E402

TRACE, LOWER, COMPILE = "pt.compile.trace", "pt.compile.lower", "pt.compile"
#: the ring's clock and jax's (``time.time``) are tied by one anchor taken
#: at import; the two drift by microseconds a minute
CLOCK_SLACK_S = 1e-3


def _named(name):
    return [s for s in host_spans() if s.name == name]


def _fresh_jits():
    """A jitted function no earlier test compiled, calling an inner jit
    twice: the names carry no cache across tests (jit caches by function
    object)."""
    @jax.jit
    def inner(x):
        return x * 2 + 1

    @jax.jit
    def outer(x):
        return inner(x) + inner(x + 1)

    return outer


def _inside(span, ev) -> bool:
    return (ev.t0 - CLOCK_SLACK_S <= span.t0
            and span.t0 + span.dur <= ev.t0 + ev.dur + CLOCK_SLACK_S)


# -- the spans --------------------------------------------------------------

def test_a_fresh_jit_leaves_its_three_spans_under_the_open_event():
    outer, x = _fresh_jits(), jnp.ones((4,))
    profiler.start_timeline()
    with RecordEvent("caused_it"):
        outer(x).block_until_ready()
    (ev,) = _named("caused_it")
    (compile_,), (lower,), (trace,) = (_named(COMPILE), _named(LOWER),
                                       _named(TRACE))
    assert compile_.counts["fun"] == lower.counts["fun"] == "jit(outer)"
    assert compile_.counts["hit"] == 0
    assert compile_.counts["cache_read_s"] == 0.0
    assert trace.counts["fun"] == "outer"
    # outer, inner twice, and the operations inside them
    assert trace.counts["traces"] >= 3
    for s in (compile_, lower, trace):
        assert s.parent_id == ev.span_id and s.tid == ev.tid
        assert s.dur > 0 and _inside(s, ev), (s, ev)
    assert trace.t0 <= lower.t0 <= compile_.t0      # the pipeline's order
    assert len({s.span_id for s in host_spans()}) == 4


def test_a_second_call_leaves_none_and_a_new_shape_one_more():
    outer, x, wider = _fresh_jits(), jnp.ones((4,)), jnp.ones((5,))
    outer(x)
    profiler.start_timeline()
    outer(x).block_until_ready()
    assert host_spans() == []       # the step path pays nothing
    with RecordEvent("new_shape"):
        outer(wider).block_until_ready()
    (again,) = _named(COMPILE)
    assert again.counts["fun"] == "jit(outer)"
    assert len(_named(TRACE)) == len(_named(LOWER)) == 1


def test_nested_traces_are_not_spans_and_are_in_no_sum_twice():
    outer, x = _fresh_jits(), jnp.ones((3,))
    profiler.start_timeline()
    before = profiler.host_event_stats().get(TRACE, {"count": 0,
                                                     "total_s": 0.0})
    with RecordEvent("root"):
        outer(x).block_until_ready()
    (root,) = _named("root")
    (trace,) = _named(TRACE)         # inner's two traces are inside it
    assert trace.counts["traces"] >= 3
    kinds = _named(TRACE) + _named(LOWER) + _named(COMPILE)
    assert sum(s.dur for s in kinds) <= root.dur
    after = profiler.host_event_stats()[TRACE]
    assert after["count"] - before["count"] == 1
    assert abs(after["total_s"] - before["total_s"] - trace.dur) < 1e-9


def test_traces_inside_a_lowering_are_not_spans():
    """A lowering rule written in jax.numpy (the threefry generator's)
    traces every operation it uses, hundreds an initialiser: they are the
    lowering's seconds, not spans."""
    profiler.start_timeline()
    jax.random.normal(jax.random.PRNGKey(7), (3, 5, 7)).block_until_ready()
    lowers, traces = _named(LOWER), _named(TRACE)
    assert lowers and len(traces) <= len(lowers)
    for t in traces:
        assert not any(lo.t0 <= t.t0 < lo.t0 + lo.dur for lo in lowers)


def test_the_parent_is_the_compiling_threads_own_event():
    outer, x = _fresh_jits(), jnp.ones((6,))
    profiler.start_timeline()

    def worker():
        with RecordEvent("worker_root"):
            outer(x).block_until_ready()

    t = threading.Thread(target=worker)
    with RecordEvent("main_root"):
        t.start()
        t.join(60)
    assert not t.is_alive()
    by = {s.name: s for s in host_spans()}
    assert by[COMPILE].parent_id == by["worker_root"].span_id
    assert by[COMPILE].tid == by["worker_root"].tid != by["main_root"].tid


def test_a_root_compile_has_parent_zero_and_is_exported(tmp_path):
    import json

    outer, x = _fresh_jits(), jnp.ones((7,))
    profiler.start_timeline()
    outer(x).block_until_ready()
    assert {s.parent_id for s in host_spans()} == {0}
    path = profiler.export_chrome_tracing(str(tmp_path / "t.json"))
    events = json.load(open(path))["traceEvents"]
    (ev,) = [e for e in events if e["name"] == COMPILE]
    assert ev["args"]["fun"] == "jit(outer)" and ev["args"]["hit"] == 0


def test_installing_the_listener_again_installs_nothing():
    assert profiler.install_compile_listener() is False
    outer, x = _fresh_jits(), jnp.ones((8,))
    profiler.start_timeline()
    outer(x).block_until_ready()
    assert len(_named(COMPILE)) == len(_named(LOWER)) == 1
    assert len(_named(TRACE)) == 1


# -- the persistent cache, and the counters ---------------------------------

@pytest.fixture
def cache_dir(tmp_path):
    """A persistent compile cache of this test's own that takes every
    program; what was configured comes back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = {"jax_compilation_cache_dir": str(tmp_path / "cache"),
            "jax_enable_compilation_cache": True,
            "jax_persistent_cache_min_compile_time_secs": 0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        yield keys["jax_compilation_cache_dir"]
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_cached_program_reads_hit_1_and_the_counters_move_alike(cache_dir):
    outer, x = _fresh_jits(), jnp.ones((9,))
    profiler.start_timeline()
    c0 = profiler.compile_counts()
    outer(x).block_until_ready()                # compiled, and written
    c1 = profiler.compile_counts()
    (cold,) = _named(COMPILE)
    assert cold.counts["hit"] == 0 and cold.counts["cache_read_s"] == 0.0
    assert os.listdir(cache_dir)
    assert {k: c1[k] - c0[k] for k in c1} == {
        "requests": 1, "cache_hits": 0, "cache_written": 1}

    jax.clear_caches()                          # in memory only
    profiler.start_timeline()
    with RecordEvent("again"):
        outer(x).block_until_ready()
    c2 = profiler.compile_counts()
    (warm,) = _named(COMPILE)
    (ev,) = _named("again")
    assert warm.counts["fun"] == "jit(outer)" and warm.counts["hit"] == 1
    assert 0 < warm.counts["cache_read_s"] <= warm.dur
    assert warm.parent_id == ev.span_id and _inside(warm, ev)
    assert len(_named(TRACE)) == len(_named(LOWER)) == 1   # traced anew
    assert {k: c2[k] - c1[k] for k in c2} == {
        "requests": 1, "cache_hits": 1, "cache_written": 0}


def test_the_counters_are_the_registrys():
    """The handles are pre-bound ``obs.registry`` counters: the exporter,
    the time series and an SLO rule see a recompiling job."""
    from paddle_tpu.obs import registry

    assert isinstance(profiler._REQUESTS, registry.Counter)
    outer, x, wider = _fresh_jits(), jnp.ones((10,)), jnp.ones((11,))
    r0 = profiler._REQUESTS.value
    h0, m0 = profiler._CACHE_HITS.value, profiler._CACHE_MISSES.value
    profiler.start_timeline()
    outer(x).block_until_ready()
    outer(wider).block_until_ready()
    spans = _named(COMPILE)
    assert profiler._REQUESTS.value - r0 == len(spans) == 2
    assert profiler._CACHE_HITS.value - h0 == sum(
        s.counts["hit"] for s in spans)
    assert profiler._CACHE_MISSES.value - m0 <= 2
    assert profiler.compile_counts() == {
        "requests": profiler._REQUESTS.value,
        "cache_hits": profiler._CACHE_HITS.value,
        "cache_written": profiler._CACHE_MISSES.value}


def test_native_build_is_a_span_that_says_whether_make_built():
    from paddle_tpu.ps import native

    assert native.native_available()        # built, if it was not
    profiler.start_timeline()
    assert native.build_native()
    (span,) = _named("pt.native.build")
    assert span.counts == {"built": 0}      # make found it current


# -- the benchmark's readers, on fabricated rings ----------------------------

SPAN_READERS = ("setup_trace_s", "setup_lower_s", "setup_compile_s",
                "setup_cache_read_s", "setup_compile_misses")
T0 = 100.0


def _span(name, t0, dur, tid=1, **counts):
    return HostSpan(name, t0, dur, 0, 0, tid, counts)


def _read(monkeypatch, metric, ring, spans=None):
    monkeypatch.setattr(profiler, "host_spans", lambda: list(ring))
    ctx = {"window": {"t0": T0}, "spans": spans or {}}
    return spec.load_module("metrics", metric).read(ctx)


RING = [
    _span("pt.pass.begin", 1.0, 50.0, keys=3),
    # an eager operation compiled while `step` is traced: its lowering
    # and its compile start inside the trace and are taken out of it
    _span(TRACE, 10.0, 8.0, fun="step", traces=900),
    _span(LOWER, 12.0, 1.0, fun="jit(iota)"),
    _span(COMPILE, 13.0, 2.0, fun="jit(iota)", hit=0, cache_read_s=0.0),
    _span(LOWER, 20.0, 3.0, fun="jit(step)"),
    _span(COMPILE, 24.0, 5.0, fun="jit(step)", hit=1, cache_read_s=4.0),
    # another thread's, overlapping the main thread's: its own nesting
    _span(TRACE, 11.0, 0.5, tid=2, fun="feed", traces=1),
    # after t0: the check's programs
    _span(TRACE, T0 + 1.0, 30.0, fun="reference", traces=5),
    _span(LOWER, T0 + 31.0, 7.0, fun="jit(reference)"),
    _span(COMPILE, T0 + 40.0, 60.0, fun="jit(reference)", hit=0,
          cache_read_s=0.0),
    _span(COMPILE, T0 + 101.0, 9.0, fun="jit(check)", hit=1,
          cache_read_s=8.0),
]
WANT = {"setup_trace_s": 8.0 - 1.0 - 2.0 + 0.5, "setup_lower_s": 1.0 + 3.0,
        "setup_compile_s": 2.0, "setup_cache_read_s": 5.0,
        "setup_compile_misses": 1}


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_reader_sums_self_time_of_what_started_before_t0(monkeypatch, metric,
                                                         capsys):
    assert _read(monkeypatch, metric, RING) == pytest.approx(WANT[metric])
    said = capsys.readouterr().out
    assert '"setup_spans"' in said and '"ring": 11' in said


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_reader_is_none_without_compile_spans(monkeypatch, metric):
    """The parent's program: a ring, and not one ``pt.compile*`` in it."""
    ring = [_span("pt.pass.begin", 1.0, 50.0), _span("train_step", 60.0, 1.0)]
    assert _read(monkeypatch, metric, ring) is None
    assert _read(monkeypatch, metric, []) is None
    monkeypatch.delattr(profiler, "host_spans")     # older still: no ring
    ctx = {"window": {"t0": T0}, "spans": {}}
    assert spec.load_module("metrics", metric).read(ctx) is None


@pytest.mark.parametrize("metric", SPAN_READERS)
def test_reader_is_zero_with_spans_of_another_kind_only(monkeypatch, metric):
    others = {"setup_trace_s": [LOWER], "setup_lower_s": [TRACE],
              "setup_compile_s": [TRACE], "setup_cache_read_s": [LOWER],
              "setup_compile_misses": [LOWER]}[metric]
    ring = [_span(k, 5.0 + i, 0.5, fun="f", traces=1)
            for i, k in enumerate(others)]
    if metric in ("setup_compile_s", "setup_compile_misses"):
        ring.append(_span(COMPILE, 9.0, 2.0, fun="f", hit=1,
                          cache_read_s=1.0))
    if metric == "setup_cache_read_s":
        ring.append(_span(COMPILE, 9.0, 2.0, fun="f", hit=0,
                          cache_read_s=0.0))
    got = _read(monkeypatch, metric, ring)
    assert got == 0 and got is not None
    # and a span of its own kind AFTER t0 changes nothing
    own = {"setup_trace_s": _span(TRACE, T0 + 1, 3.0, fun="g", traces=1),
           "setup_lower_s": _span(LOWER, T0 + 1, 3.0, fun="g"),
           "setup_compile_s": _span(COMPILE, T0 + 1, 3.0, fun="g", hit=0,
                                    cache_read_s=0.0),
           "setup_cache_read_s": _span(COMPILE, T0 + 1, 3.0, fun="g", hit=1,
                                       cache_read_s=2.0),
           "setup_compile_misses": _span(COMPILE, T0 + 1, 3.0, fun="g",
                                         hit=0, cache_read_s=0.0)}[metric]
    assert _read(monkeypatch, metric, ring + [own]) == 0


def test_self_seconds_nests_per_thread_and_never_goes_negative():
    a = _span(TRACE, 0.0, 10.0)
    b = _span(LOWER, 1.0, 4.0)          # inside a
    c = _span(COMPILE, 2.0, 2.0)        # inside b: out of b, not out of a
    d = _span(COMPILE, 6.0, 30.0)       # starts inside a, outlasts it
    e = _span(TRACE, 3.0, 1.0, tid=9)   # another thread's
    got = {id(s): own for s, own in setup_spans.self_seconds([d, c, a, e, b])}
    assert got[id(a)] == 0.0            # 10 - 4 - 30, held at 0
    assert got[id(b)] == 2.0 and got[id(c)] == 2.0
    assert got[id(d)] == 30.0 and got[id(e)] == 1.0


def test_devices_reader_is_the_harness_clock():
    read = spec.load_module("metrics", "setup_devices_s").read
    assert read({"spans": {"devices_s": 9.25}}) == 9.25
    assert read({"spans": {}}) is None


def test_rows_walked_share_reads_the_steps_own_buffers():
    import numpy as np

    read = spec.load_module("metrics", "moe_rows_walked_share").read
    buffers = {"dispatch_rows_walked": np.array([4096, 5120, 4096, 4096]),
               "dispatch_rung": np.array([8192, 8192, 8192, 8192])}
    system = types.SimpleNamespace(trainer=types.SimpleNamespace(
        state={"params": {}, "buffers": buffers}))
    assert read({"system": system}) == pytest.approx(17408 / 32768)
    del buffers["dispatch_rows_walked"]          # a step without the counter
    assert read({"system": system}) is None
    assert read({"system": types.SimpleNamespace()}) is None


FLASH = "pt.flash.operands"
_WIDTHS = dict(bits=16, head_dim=128, lanes=128, v_head_dim=128)


def test_pairs_walked_share_sums_the_calls_set_up_traced(monkeypatch):
    """``flash_pairs_walked_share``: walked over rectangle, over the
    ``pt.flash.operands`` spans that start before ``t0`` — the check's
    programs, traced after the window, are not the step's calls."""
    listed = dict(_WIDTHS, pairs_walked=36, pairs_rectangle=64)
    ring = [_span(FLASH, 10.0 + i, 0.01, **listed) for i in range(6)]
    assert _read(monkeypatch, "flash_pairs_walked_share", ring) == 0.5625
    after = _span(FLASH, T0 + 5.0, 0.01,
                  **dict(_WIDTHS, pairs_walked=64, pairs_rectangle=64))
    assert _read(monkeypatch, "flash_pairs_walked_share",
                 ring + [after]) == 0.5625
    whole = dict(_WIDTHS, pairs_walked=1, pairs_rectangle=1)     # ERNIE's
    assert _read(monkeypatch, "flash_pairs_walked_share",
                 [_span(FLASH, 10.0, 0.01, **whole)] * 36) == 1.0
    mixed = ring[:1] + [_span(FLASH, 20.0, 0.01, **dict(
        _WIDTHS, pairs_walked=64, pairs_rectangle=64))]
    assert _read(monkeypatch, "flash_pairs_walked_share",
                 mixed) == pytest.approx(100 / 128)


def test_pairs_walked_share_is_none_on_a_span_without_the_counts(monkeypatch):
    """The parent's program: ``pt.flash.operands`` with the widths alone;
    a program with no flash call; one older than the ring."""
    ring = [_span(FLASH, 10.0, 0.01, **_WIDTHS),
            _span(TRACE, 11.0, 2.0, fun="step", traces=3)]
    assert _read(monkeypatch, "flash_pairs_walked_share", ring) is None
    assert _read(monkeypatch, "flash_pairs_walked_share", ring[1:]) is None
    assert _read(monkeypatch, "flash_pairs_walked_share", []) is None
    monkeypatch.delattr(profiler, "host_spans")
    ctx = {"window": {"t0": T0}, "spans": {}}
    assert spec.load_module(
        "metrics", "flash_pairs_walked_share").read(ctx) is None


def test_pairs_walked_share_reads_the_programs_own_spans():
    """From the ring itself: a causal call in 4 x 4 blocks walks 10 of 16
    pairs, a bidirectional one beside it all 16 — one span a call a trace,
    none from the warm calls."""
    from paddle_tpu.ops.flash_attention import flash_attention

    q = jnp.ones((1, 64, 2, 8))

    @jax.jit
    def both(q):
        kw = dict(block_q=16, block_k=16, interpret=True)
        return (flash_attention(q, q, q, causal=True, **kw)
                + flash_attention(q, q, q, **kw))

    profiler.start_timeline()
    for _ in range(2):
        jax.block_until_ready(both(q))
    assert [(s.counts["pairs_walked"], s.counts["pairs_rectangle"])
            for s in _named(FLASH)] == [(10, 16), (16, 16)]
    read = spec.load_module("metrics", "flash_pairs_walked_share").read
    now = max(s.t0 + s.dur for s in _named(FLASH)) + 1.0
    assert read({"window": {"t0": now}}) == pytest.approx(26 / 32)
    assert read({"window": {"t0": 0.0}}) is None      # all after this t0


def test_benchmark_lists_the_new_metrics_in_every_cell():
    bench = spec.load_benchmark()
    assert spec.check_contract(bench) == []
    cells = [w["name"] for w in bench["workloads"]]
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_READERS + ("setup_devices_s",):
        m = by[name]
        assert m["workloads"] == cells and m["moves"] == "setup_s"
        assert m["layer"] == "entry points" and m["better"] == "lower"
    assert by["moe_rows_walked_share"]["workloads"] == [
        "joyai_flash_seq4096", "lfm2_8b_a1b_seq4096",
        "smallthinker_21b_seq16384",                     # PR 44: appended
        "xing4_29b_a4b_seq4096"]                         # PR 51: appended
    walked = by["flash_pairs_walked_share"]              # PR 41
    assert walked["workloads"] == [
        "ernie_base_seq512", "olmoe_1b7b_seq4096", "joyai_flash_seq4096",
        "lfm2_8b_a1b_seq4096", "smallthinker_21b_seq16384",
        "evabyte_6b5_seq8192",                           # PR 46: appended
        "xing4_29b_a4b_seq4096"]                         # PR 51: appended
    assert (walked["layer"], walked["moves"], walked["source"]) == (
        "kernels", "tokens_per_s_per_chip", "program_counter")
    # PR 44's seven come after them, PR 46's six after those, PR 51's
    # five after those: nothing was put in the middle
    assert [m["name"] for m in bench["per_layer"][-26:-18]] == list(
        SPAN_READERS) + ["setup_devices_s", "moe_rows_walked_share",
                         "flash_pairs_walked_share"]
    assert all(m["workloads"] == ["smallthinker_21b_seq16384"]
               for m in bench["per_layer"][-18:-11])
    assert all(m["workloads"] == ["evabyte_6b5_seq8192"]
               for m in bench["per_layer"][-11:-5])
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "mhc_mfu", "mhc_residual_share", "mhc_map_share",
        "mhc_hbm_roofline", "mhc_res_err"]
    assert all(m["workloads"] == ["xing4_29b_a4b_seq4096"]
               and m["moves"] == "tokens_per_s_per_chip"
               for m in bench["per_layer"][-5:])

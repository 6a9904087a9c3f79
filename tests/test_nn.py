import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn


def test_linear_shapes_and_registration():
    pt.seed(0)
    layer = nn.Linear(4, 3)
    y = layer(jnp.ones((2, 4)))
    assert y.shape == (2, 3)
    names = dict(layer.named_parameters())
    assert set(names) == {"weight", "bias"}


def test_sublayer_traversal_and_state_dict():
    pt.seed(0)
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    sd = model.state_dict()
    assert "0.weight" in sd and "2.bias" in sd
    # round-trip
    sd2 = {k: np.asarray(v) + 1 for k, v in sd.items()}
    model.set_state_dict(sd2)
    assert np.allclose(np.asarray(model.state_dict()["0.weight"]), sd2["0.weight"])


def test_functional_call_pure():
    pt.seed(0)
    model = nn.Linear(4, 2)
    state = nn.get_state(model)
    zeros = {"params": {k: jnp.zeros_like(v) for k, v in state["params"].items()}, "buffers": {}}
    out, _ = nn.functional_call(model, zeros, jnp.ones((1, 4)))
    assert np.allclose(np.asarray(out), 0.0)
    # original params restored after functional_call
    out2 = model(jnp.ones((1, 4)))
    assert not np.allclose(np.asarray(out2), 0.0)


def test_batchnorm_buffers_update_in_training():
    pt.seed(0)
    bn = nn.BatchNorm2D(3)
    x = jnp.asarray(np.random.default_rng(0).normal(2.0, 1.0, (4, 3, 5, 5)).astype(np.float32))
    bn.train()
    y = bn(x)
    assert y.shape == x.shape
    assert not np.allclose(np.asarray(bn._mean), 0.0)  # running mean moved
    bn.eval()
    y2 = bn(x)
    assert y2.shape == x.shape


def test_dropout_train_vs_eval():
    pt.seed(0)
    d = nn.Dropout(0.5)
    x = jnp.ones((100,))
    d.train()
    y = d(x)
    assert float(jnp.sum(y == 0)) > 0
    d.eval()
    assert np.allclose(np.asarray(d(x)), 1.0)


def test_conv_pool_shapes():
    pt.seed(0)
    conv = nn.Conv2D(1, 6, 3, padding=1)
    x = jnp.ones((2, 1, 28, 28))
    y = conv(x)
    assert y.shape == (2, 6, 28, 28)
    p = nn.functional.max_pool2d(y, 2, 2)
    assert p.shape == (2, 6, 14, 14)
    a = nn.functional.avg_pool2d(y, 2, 2)
    assert a.shape == (2, 6, 14, 14)


def test_cross_entropy_matches_manual():
    logits = jnp.asarray([[2.0, 1.0, 0.1]])
    labels = jnp.asarray([0])
    loss = nn.functional.cross_entropy(logits, labels)
    manual = -jax.nn.log_softmax(logits)[0, 0]
    assert np.allclose(float(loss), float(manual), atol=1e-6)


def test_embedding_padding_idx():
    pt.seed(0)
    emb = nn.Embedding(10, 4, padding_idx=0)
    out = emb(jnp.asarray([[0, 1]]))
    assert np.allclose(np.asarray(out[0, 0]), 0.0)
    assert not np.allclose(np.asarray(out[0, 1]), 0.0)


def test_layer_norm():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32))
    ln = nn.LayerNorm(8)
    y = ln(x)
    assert np.allclose(np.asarray(y.mean(-1)), 0.0, atol=1e-5)


def test_auto_cast_linear_and_conv_compute_bf16():
    """amp.auto_cast's contract: dense ops consult the amp state at
    trace time — the matmul/conv runs in bf16 with f32 accumulation and
    the output (and gradients) stay f32."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu import amp
    from paddle_tpu.nn import functional as F

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    b = jnp.zeros((8,), jnp.float32)
    ref = F.linear(x, w, b)
    with amp.auto_cast(enable=True):
        out = jax.jit(F.linear)(x, w, b)
        g = jax.jit(jax.grad(lambda w: F.linear(x, w, b).sum()))(w)
    assert out.dtype == jnp.float32 and g.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    # the cast must actually be in the traced program (backend-neutral
    # check: on TPU the DEFAULT precision also rounds to bf16, so value
    # comparison can't distinguish the paths). Fresh wrapper per mode:
    # jax caches traces per function object, so re-tracing F.linear
    # itself would replay the amp-on jaxpr — the exact trace-time
    # pitfall auto_cast's docstring warns about.
    with amp.auto_cast(enable=True):
        jaxpr_on = str(jax.make_jaxpr(lambda x, w, b: F.linear(x, w, b))(x, w, b))
    assert "bfloat16" in jaxpr_on, jaxpr_on
    jaxpr_off = str(jax.make_jaxpr(lambda x, w, b: F.linear(x, w, b))(x, w, b))
    assert "bfloat16" not in jaxpr_off, jaxpr_off

    xc = jnp.asarray(rng.normal(size=(2, 3, 8, 8)).astype(np.float32))
    wc = jnp.asarray(rng.normal(size=(4, 3, 3, 3)).astype(np.float32))
    refc = F.conv2d(xc, wc)
    with amp.auto_cast(enable=True):
        outc = jax.jit(lambda x, w: F.conv2d(x, w))(xc, wc)
    assert outc.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(outc), np.asarray(refc),
                               rtol=5e-2, atol=5e-2)


def _parent_amp_linear(x, w, b=None):
    """``linear``'s amp branch before its backward was stated: the
    gradient left to the transpose of the two ``astype``s."""
    y = jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    return y if b is None else y + b


def _linear_case(name):
    """(x, w, b, wrap): ``wrap(linear)`` is the function differentiated.
    The weight is 4096 wide on both sides: ``linear`` states the backward
    of such a layer, and leaves a narrower one's to ``jax.grad``. The
    ``head*`` cases go through ``lm_head``, which states the backward of a
    weight narrower than that too (here 256 x 640): called once, on the
    transpose of an embedding table, twice on one weight."""
    rng = np.random.default_rng(7)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    if name.startswith("head"):
        x, w = f32(2, 4, 256), f32(256, 640) / 16
        if name == "head":
            return x, w, None, lambda head: head
        if name == "head_tied":
            return x, w.T, None, lambda head: lambda x, e: head(x, e.T)
        assert name == "head_two_calls"
        return x, w, None, lambda head: lambda x, w: (
            head(x, w) + 0.3 * head(jnp.tanh(x), w))
    # y of unit size, so that sin's slope does not turn on y's rounding
    w, b = f32(4096, 4096) / 64, f32(4096)
    plain = lambda linear: linear
    if name == "rank2":
        return f32(8, 4096), w, None, plain
    if name == "rank2_bias":
        return f32(8, 4096), w, b, plain
    if name == "batch_length_hidden":
        return f32(2, 4, 4096), w, None, plain
    if name == "batch_length_hidden_bias":
        return f32(2, 4, 4096), w, b, plain
    if name == "checkpoint":
        return f32(2, 4, 4096), w, b, lambda linear: jax.checkpoint(
            lambda *a: linear(*a))
    assert name == "vmap"
    # a batch of inputs AND of weights: the rule's own batching
    return (f32(2, 3, 4096), f32(2, 4096, 4096) / 64, None,
            lambda linear: jax.vmap(lambda x, w: linear(x, w)))


def _written_out(x, w, g):
    """The stated backward as a formula: (dx, dW) of ``y = x16 @ w16``
    under the cotangent ``g``, one bf16 ``g`` for both, float32 sums."""
    bf16 = jnp.bfloat16
    g16, x16 = g.astype(bf16), x.astype(bf16)
    dx = jnp.matmul(g16, w.astype(bf16).T, preferred_element_type=jnp.float32)
    dw = jnp.matmul(x16.reshape(-1, x.shape[-1]).T,
                    g16.reshape(-1, g.shape[-1]),
                    preferred_element_type=jnp.float32)
    return dx, dw


@pytest.mark.parametrize("case", ["rank2", "rank2_bias", "batch_length_hidden",
                                  "batch_length_hidden_bias", "checkpoint",
                                  "vmap", "head", "head_tied",
                                  "head_two_calls"])
def test_amp_linear_backward_is_stated(case):
    """Under ``auto_cast`` the backward of ``linear`` is its own statement
    (one bf16 cotangent for both matmuls, the forward's bf16 ``x``,
    float32 out): every gradient is float32, agrees with the float32
    gradient within bf16's rounding of the operands and with the parent's
    expression closer than that — and the weight's gradient is NOT a bf16
    number widened, as the parent's was."""
    from paddle_tpu import amp
    from paddle_tpu.nn import functional as F

    x, w, b, wrap = _linear_case(case)
    args = (x, w) if b is None else (x, w, b)
    ours = F.lm_head if case.startswith("head") else F.linear
    # a smooth function of y whose cotangent is no constant
    loss = lambda linear: lambda *a: jnp.sum(jnp.sin(wrap(linear)(*a)))
    argnums = tuple(range(len(args)))
    with amp.auto_cast(enable=True):
        out = wrap(lambda *a: ours(*a))(*args)
        got = jax.grad(loss(lambda *a: ours(*a)), argnums)(*args)
    parent = jax.grad(loss(_parent_amp_linear), argnums)(*args)
    exact = jax.grad(loss(lambda x, w, b=None: F.linear(x, w, b)),
                     argnums)(*args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(
        wrap(_parent_amp_linear)(*args)))          # the forward: to the bit
    for g, p, e in zip(got, parent, exact):
        assert g.dtype == jnp.float32
        scale = float(jnp.abs(e).max())
        # operands rounded to 8 bits of mantissa, sums of 8 to 4096 terms;
        # the float32 gradient is also taken at an unrounded y
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   atol=0.05 * scale, rtol=0.05)
        # against the parent only the cotangent's rounding differs
        np.testing.assert_allclose(np.asarray(g), np.asarray(p),
                                   atol=0.01 * scale, rtol=0.02)
    widened = lambda a: np.asarray(a.astype(jnp.bfloat16).astype(jnp.float32))
    if case != "head_two_calls":    # there the parent's is a sum of two
        np.testing.assert_array_equal(np.asarray(parent[1]),
                                      widened(parent[1]))
    assert (np.asarray(got[1]) != widened(got[1])).mean() > 0.9
    if case in ("rank2", "batch_length_hidden", "head"):
        for g, f in zip(got, _written_out(x, w, jnp.cos(out))):
            np.testing.assert_allclose(np.asarray(g), np.asarray(f),
                                       rtol=1e-6, atol=1e-6 * float(
                                           jnp.abs(f).max()))


def test_linear_without_amp_or_with_bf16_input_is_the_plain_matmul():
    """Amp off, and a bf16 ``x`` under amp, take the branch they took:
    ``matmul(x, w) + b`` to the bit, out and gradients, with no custom
    rule in the trace."""
    from paddle_tpu import amp
    from paddle_tpu.nn import functional as F

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 16, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    plain = lambda x, w, b: jnp.sum(jnp.sin(jnp.matmul(x, w) + b))
    ours = lambda x, w, b: jnp.sum(jnp.sin(F.linear(x, w, b)))
    head = lambda x, w, b: jnp.sum(jnp.sin(F.lm_head(x, w) + b))

    def same(args, ours=ours):
        for got, want in zip(
                jax.tree_util.tree_leaves(
                    jax.value_and_grad(ours, (0, 1, 2))(*args)),
                jax.tree_util.tree_leaves(
                    jax.value_and_grad(plain, (0, 1, 2))(*args))):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(want.astype(jnp.float32)))
        assert "custom_vjp" not in str(jax.make_jaxpr(
            lambda *a: ours(*a))(*args))

    same((x, w, b))
    same((x, w, b), head)
    with amp.auto_cast(enable=True):
        for call in (ours, head):
            same((x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                  b.astype(jnp.bfloat16)), call)


def test_amp_linear_leaves_one_span_a_traced_call():
    """``pt.linear.amp`` (in, out, the operands' bits, whether the backward
    is stated) is recorded where the branch is taken: once a traced call —
    a layer under ``jax.checkpoint`` and a gradient included — and never
    on the step path."""
    from paddle_tpu import amp
    from paddle_tpu.core import profiler
    from paddle_tpu.nn import functional as F

    x = jnp.ones((4, 4096), jnp.float32)
    w1, w2 = jnp.ones((4096, 4096), jnp.float32), jnp.ones((4096, 8), jnp.float32)

    def loss(w1, w2):
        with amp.auto_cast(enable=True):
            h = jax.checkpoint(lambda x, w: F.linear(x, w))(x, w1)
            return jnp.sum(F.linear(h, w2))

    step = jax.jit(jax.grad(loss, (0, 1)))
    spans = lambda: [s.counts for s in profiler.host_spans()
                     if s.name == "pt.linear.amp"]
    profiler.start_timeline()
    jax.block_until_ready(step(w1, w2))
    assert spans() == [
        {"in_features": 4096, "out_features": 4096, "bits": 16,
         "stated_backward": 1},
        {"in_features": 4096, "out_features": 8, "bits": 16,
         "stated_backward": 0}]
    jax.block_until_ready(step(w1, w2))
    assert len(spans()) == 2
    profiler.start_timeline()
    F.linear(x, w1)                     # amp off: not in the branch
    assert spans() == []


def test_amp_linear_with_a_stated_backward_is_reverse_mode_only():
    """A stated backward is a ``custom_vjp``: forward mode of such a layer
    raises (jax's own TypeError). A narrower layer's, and amp off, still
    differentiate forward."""
    from paddle_tpu import amp
    from paddle_tpu.nn import functional as F

    x = jnp.ones((4, 4096), jnp.float32)
    wide, narrow = jnp.ones((4096, 4096), jnp.float32), jnp.ones((4096, 8), jnp.float32)
    jax.jvp(lambda x: F.linear(x, wide), (x,), (x,))
    # a head: 256 -> 640, narrower than 4096
    xh, head = jnp.ones((4, 256), jnp.float32), jnp.ones((256, 640), jnp.float32)
    jax.jvp(lambda x: F.lm_head(x, head), (xh,), (xh,))
    with amp.auto_cast(enable=True):
        jax.jvp(lambda x: F.linear(x, narrow), (x,), (x,))
        with pytest.raises(TypeError, match="custom_vjp"):
            jax.jvp(lambda x: F.linear(x, wide), (x,), (x,))
        with pytest.raises(TypeError, match="custom_vjp"):
            jax.jvp(lambda x: F.lm_head(x, head), (xh,), (xh,))


def test_amp_linear_states_its_backward_from_a_width_up():
    """The choice is made from the weight's shape, where the branch is
    taken, and the span says which way: with a side narrower than 4096 the
    trace is the parent's expression to the letter (the gradient left to
    ``jax.grad``), else the stated rule's."""
    from paddle_tpu import amp
    from paddle_tpu.core import profiler
    from paddle_tpu.nn import functional as F

    x = jnp.ones((4, 4096), jnp.float32)
    shapes = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)

    def grad_text(linear, w):
        return str(jax.make_jaxpr(jax.grad(
            lambda x, w: jnp.sum(linear(x, w) ** 2), (0, 1)))(x, w))

    profiler.start_timeline()
    with amp.auto_cast(enable=True):
        ours = [grad_text(lambda x, w: F.linear(x, w), shapes(4096, out))
                for out in (4095, 2560, 4096, 11008)]
    for text, out in zip(ours[:2], (4095, 2560)):
        assert text == grad_text(_parent_amp_linear, shapes(4096, out))
        assert "optimization_barrier" not in text
    assert all("optimization_barrier" in text for text in ours[2:])
    assert [s.counts["stated_backward"] for s in profiler.host_spans()
            if s.name == "pt.linear.amp"] == [0, 0, 1, 1]


def _small_decoder(name):
    """(model, its head weight's (in, out), how many times the head's
    backward is stated) of each decoder at tiny widths, every weight
    narrower than 4096: ``linear`` states no layer's backward there,
    ``lm_head`` always. SmallThinker's head calls ``lm_head``, JoyAI's one
    weight twice; the other three keep ``linear`` for theirs, each for its
    cell's reading (the call sites say why; PERF.md section 6, PR 49)."""
    from paddle_tpu.models import evabyte, joyai, lfm2, olmoe, smallthinker

    if name == "smallthinker":
        return smallthinker.SmallThinker(smallthinker.SmallThinkerConfig(
            vocab_size=97, hidden_size=32, num_heads=4, num_kv_heads=2,
            head_dim=8, sliding_window_size=6, num_layers=4, router_width=8,
            experts_per_token=2, expert_size=16, held=(2, 2), max_seq_len=24,
            total_layers=52)), (32, 97), 1
    if name == "olmoe":
        return olmoe.Olmoe(olmoe.OlmoeConfig(
            vocab_size=97, hidden_size=32, num_heads=4, num_layers=2,
            num_experts=8, experts_per_token=2, expert_size=16,
            max_seq_len=16)), (32, 97), 0
    if name == "lfm2":
        return lfm2.Lfm2(lfm2.Lfm2Config(
            vocab_size=97, hidden_size=32, num_heads=4, num_kv_heads=2,
            dense_size=48, num_experts=8, experts_per_token=2,
            expert_size=16, max_seq_len=16,
            layer_types=("conv", "full_attention", "conv"),
            num_dense_layers=1, held=(2, 2))), (32, 97), 0
    if name == "joyai":
        return joyai.Joyai(joyai.JoyaiConfig(
            vocab_size=97, hidden_size=32, num_heads=4, num_layers=3,
            dense_size=48, q_rank=24, kv_rank=16, nope_dim=16, rope_dim=8,
            v_dim=16, num_experts=8, experts_per_token=2, expert_size=16,
            max_seq_len=16, held=(2, 2))), (32, 97), 2
    assert name == "evabyte"
    return evabyte.EvaByte(evabyte.EvaByteConfig(
        vocab_size=7, hidden_size=32, num_heads=4, intermediate_size=48,
        num_layers=2, window_size=16, chunk_size=4, num_pred_heads=3,
        max_seq_len=64, total_layers=32)), (32, 3 * 7), 0


@pytest.mark.parametrize("name", ["smallthinker", "olmoe", "lfm2", "joyai",
                                  "evabyte"])
def test_decoder_states_its_heads_backward_and_no_projections(name):
    """Each decoder traced under amp: the ``pt.linear.amp`` span of a
    vocabulary head that calls ``lm_head`` reads ``stated_backward`` 1,
    every other layer's — the projections and FFN weights, all narrower
    than 4096 here, and a head that keeps ``linear`` — 0."""
    from paddle_tpu import amp, nn
    from paddle_tpu.core import profiler

    model, head, stated_calls = _small_decoder(name)
    state = nn.get_state(model)
    ids = jnp.zeros((1, 16), jnp.int32)

    def forward(state, ids):
        with amp.auto_cast(enable=True):
            return nn.functional_call(model, state, ids)[0]

    profiler.start_timeline()
    jax.eval_shape(forward, state, ids)
    spans = [s.counts for s in profiler.host_spans()
             if s.name == "pt.linear.amp"]
    stated = [(s["in_features"], s["out_features"]) for s in spans
              if s["stated_backward"]]
    assert stated == [head] * stated_calls
    assert (head[0], head[1]) in [(s["in_features"], s["out_features"])
                                  for s in spans]
    assert len(spans) > len(stated)
    assert all(s["bits"] == 16 for s in spans)


def test_ernie_and_a_ctr_tower_trace_to_the_parents_expression(monkeypatch):
    """The models this PR leaves alone. A CTR tower's ``nn.Linear`` layers
    (narrow: their backward is ``jax.grad``'s) differentiate to the jaxpr
    of the parent's expression to the letter; ERNIE writes raw ``@`` and
    traces to the same program whether or not ``linear`` and ``lm_head``
    exist, with no span and no custom rule."""
    from paddle_tpu import amp, nn
    from paddle_tpu.core import profiler
    from paddle_tpu.models.ctr import CtrConfig, DeepFM
    from paddle_tpu.models.ernie import Ernie, ErnieConfig
    from paddle_tpu.nn import functional as F

    def grad_text(model, *inputs):
        state = nn.get_state(model)

        def loss(params):
            with amp.auto_cast(enable=True):
                out = nn.functional_call(
                    model, dict(state, params=params), *inputs)[0]
            return jnp.sum(out ** 2)

        return str(jax.make_jaxpr(jax.grad(loss))(state["params"]))

    tower = DeepFM(CtrConfig(num_sparse_slots=4, embedx_dim=8, num_dense=3,
                             dnn_hidden=(64, 32)))
    tower_in = (jnp.ones((5, 4, 9)), jnp.ones((5, 3)))
    ernie = Ernie(ErnieConfig(vocab_size=32, hidden_size=16, num_heads=4,
                              ffn_size=32, num_layers=2, max_seq_len=64))
    ernie_in = (jnp.zeros((2, 8), jnp.int32),)

    profiler.start_timeline()
    ours = grad_text(tower, *tower_in), grad_text(ernie, *ernie_in)
    spans = [s.counts["stated_backward"] for s in profiler.host_spans()
             if s.name == "pt.linear.amp"]
    assert spans == [0] * 4            # the tower's four layers, ERNIE none

    def refuse(*a, **k):
        raise AssertionError("ERNIE multiplies with raw @")

    monkeypatch.setattr(F, "lm_head", refuse)
    monkeypatch.setattr(F, "linear", _parent_amp_linear)
    assert grad_text(tower, *tower_in) == ours[0]
    monkeypatch.setattr(F, "linear", refuse)
    assert grad_text(ernie, *ernie_in) == ours[1]
    for text in ours:
        assert "custom_vjp" not in text and "optimization_barrier" not in text

"""The system under test for byte-level EVA-decoder training:
``executor.Trainer`` driving the repo's ``EvaByte`` one compiled step per
dispatch, fed by the device prefetcher as ``Trainer.train_from_dataset``
feeds it. A dense model: no router, no held experts, no set-up beyond the
trainer's own — so this system stands alone beside the expert decoders'
(it shares ``_outside_the_compile_cache`` with them and nothing else).

Program surface held on to (all public): ``paddle_tpu.seed``,
``optimizer.AdamW``, ``nn.functional_call``, ``amp.step_ctx``,
``executor.Trainer`` (``train_step``, ``.state``, ``.opt_state``,
``sync_model``, ``global_step``), ``executor.make_train_step``,
``models.evabyte.{EvaByte, EvaByteConfig, evabyte_loss}``
(``forward(ids)`` -> logits [B, L, heads, vocab], ``cfg.recompute``,
``cfg.attn_precision``), ``data.prefetcher.device_prefetch``.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import Any, Dict, List

import numpy as np

from harness import spec

_outside_the_compile_cache = spec.load_module(
    "adapters", "causal_mla_moe_lm")._outside_the_compile_cache

#: ``Trainer.train_from_dataset(prefetch_depth=2)``
PREFETCH_DEPTH = 2
#: the check's AdamW step runs at the benchmark's OLMoE rate, as the other
#: decoder cells' checks do: its update is 3% of a weight of 0.01275, and a
#: wrong one (a skipped step, a halved rate, a decay left out) cannot hide
CHECK_LEARNING_RATE = 4e-4


def _model_cfg(cfg):
    from paddle_tpu.models.evabyte import EvaByteConfig

    assert cfg["attention_class"] == "eva" and cfg["norm_add_unit_offset"] \
        and cfg["rope_scaling"] is None and not cfg["tie_word_embeddings"] \
        and not cfg["attention_bias"] and cfg["hidden_act"] == "silu" \
        and cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    return EvaByteConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"], first_layer=cfg["first_layer"],
        window_size=cfg["window_size"], chunk_size=cfg["chunk_size"],
        num_pred_heads=cfg["num_pred_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        init_std=cfg["init_std"],
        total_layers=cfg["published"]["num_hidden_layers"],
        recompute=cfg["recompute"])


@contextlib.contextmanager
def float32_function(model):
    """The model's function in float32: every ``jnp`` matmul at precision
    ``highest`` and the flash kernels handed float32 operands
    (``cfg.attn_precision``), so that on the chip the walked pair list and
    the kernels' mask ARE what is compared."""
    import jax

    was = model.cfg.attn_precision
    model.cfg.attn_precision = "highest"
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        model.cfg.attn_precision = was


def function_of(model, loss_fn, use_amp: bool, grads: bool = True):
    """``(state, ids, labels) -> {loss, logits, grads}``, one compiled
    program: the loss ``loss_fn`` gives, the eight heads' logits and —
    where asked for — every gradient leaf, through ``nn.functional_call``
    as the step calls the model (its attention choice and recomputation as
    configured), under ``amp`` or as the ``float32_function``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import nn
    from paddle_tpu.amp import step_ctx

    def both(params, buffers, ids, labels):
        def total(params):
            logits, _ = nn.functional_call(
                model, {"params": params, "buffers": buffers}, ids,
                training=True)
            return loss_fn(logits, labels), logits

        with step_ctx(use_amp):
            if not grads:
                return total(params) + (None,)
            (loss, logits), leaves = jax.value_and_grad(
                total, has_aux=True)(params)
        return loss, logits, leaves

    program = jax.jit(both)

    def run(state, ids, labels) -> Dict[str, Any]:
        with contextlib.nullcontext() if use_amp else float32_function(model):
            loss, logits, leaves = program(
                state["params"], state["buffers"], jnp.asarray(ids),
                jnp.asarray(labels))
        return {"loss": float(loss), "logits": logits, "grads": leaves}

    return run


def loss_logits_grads(model, loss_fn, state, ids, labels, use_amp: bool,
                      grads: bool = True) -> Dict[str, Any]:
    """``function_of`` compiled and run once."""
    return function_of(model, loss_fn, use_amp, grads)(state, ids, labels)


class CausalEvaLmSystem:
    unit = "tokens"
    steps_per_dispatch = 1
    table_rows = None

    def __init__(self, cell, seed, devices, sizes, gen, spans) -> None:
        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.executor import Trainer
        from paddle_tpu.models import evabyte   # a program without it
        #                                         fails here, at once

        cfg = self.cfg = cell.config
        self.seed, self.spans = seed, spans
        self.seq = int(cell.traffic["seq_len"])
        assert self.seq <= cfg["max_position_embeddings"]
        B = self.batch = sizes["batch_per_chip"] * len(devices)
        self.units_per_dispatch = B * self.seq
        # one batch more than the window cycles: the check's, drawn like
        # the traffic and never trained on
        data = gen.generate(cell.traffic, seed, vocab=cfg["vocab_size"],
                            batches=sizes["host_dispatches"] + 1, batch=B)
        self.host_items = list(zip(data["ids"][:-1], data["labels"][:-1]))
        self.check_items = (data["ids"][-1][:1], data["labels"][-1][:1])
        pt.seed(seed)
        self.model = evabyte.EvaByte(_model_cfg(cfg))
        self.loss_fn = evabyte.evabyte_loss
        self.trainer = Trainer(
            self.model,
            optimizer.AdamW(learning_rate=cfg["learning_rate"],
                            weight_decay=cfg["weight_decay"],
                            beta1=cfg["beta1"], beta2=cfg["beta2"],
                            epsilon=cfg["epsilon"]),
            self.loss_fn, amp=cfg["amp"])
        # the Layer now names the trainer's arrays, not a second copy of
        # the parameters (3.1 GiB at full widths)
        self.trainer.sync_model()

    def feeder(self):
        from paddle_tpu.data.prefetcher import device_prefetch

        return device_prefetch(itertools.cycle(self.host_items),
                               depth=PREFETCH_DEPTH)

    def dispatch(self, item):
        return (self.trainer.train_step(item[0], item[1]),)

    def outcomes(self, handles):
        """(dispatches whose loss is not finite, the loss of each)."""
        import jax

        losses = [float(h[0]) for h in jax.device_get(handles)]
        return sum(not np.isfinite(x) for x in losses), losses

    def compiled_text(self) -> str:
        return ""        # harness/scopes.py asks the trainer itself

    def check_state(self) -> Dict[str, Any]:
        """Parameters after the window are finite."""
        import jax
        import jax.numpy as jnp

        ok = bool(jax.jit(lambda t: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(t)])
        ))(self.trainer.state["params"]))
        return {"ok": ok, "steps_counted": int(self.trainer.global_step)}

    def _check_programs(self):
        """The check's three programs, each compiled once and run at both
        states: the float32 function, the forward pass under ``amp`` and
        the step ``executor.make_train_step`` builds — this model, this
        loss, ``amp``, the flash kernels under the stated mask, block
        recomputation, AdamW (the configuration's betas, eps and decay at
        ``CHECK_LEARNING_RATE``)."""
        from paddle_tpu import optimizer
        from paddle_tpu.executor import make_train_step

        cfg = self.cfg
        hyper = {"lr": CHECK_LEARNING_RATE, "beta1": cfg["beta1"],
                 "beta2": cfg["beta2"], "eps": cfg["epsilon"],
                 "weight_decay": cfg["weight_decay"]}
        opt = optimizer.AdamW(learning_rate=hyper["lr"],
                              weight_decay=hyper["weight_decay"],
                              beta1=hyper["beta1"], beta2=hyper["beta2"],
                              epsilon=hyper["eps"])
        return {"f32": function_of(self.model, self.loss_fn, use_amp=False),
                "amp_forward": function_of(self.model, self.loss_fn,
                                           bool(cfg["amp"]), grads=False),
                "step": make_train_step(self.model, opt, self.loss_fn,
                                        donate=True, amp=bool(cfg["amp"])),
                "opt": opt, "hyper": hyper}

    def _step_as_measured(self, reference, programs, state, ids, labels):
        """What the window measured, on the check's sequence, from zero
        moments. Returns the loss the step returned, the gradients read
        out of its first moment (``m / (1 - beta1)``) and the reference's
        verdict on the update — and ``state``'s parameters on the host:
        the device's copy is given up to the step (donated), as the
        trainer's is."""
        import jax
        import jax.numpy as jnp

        hyper = programs["hyper"]
        before = jax.device_get(state["params"])
        new_state, new_opt, loss = programs["step"](
            state, programs["opt"].init(state["params"]), jax.random.key(0),
            (jnp.asarray(ids),), (jnp.asarray(labels),))
        slots = new_opt["slots"]
        got = {"loss": float(loss),
               "update": reference.compare_update(
                   before, new_state["params"], slots["m"], slots["v"],
                   hyper)}
        got["grads"] = jax.jit(lambda m: jax.tree_util.tree_map(
            lambda x: x / (1.0 - hyper["beta1"]), m),
            donate_argnums=(0,))(slots["m"])
        return got, before

    def check_reference(self, reference) -> Dict[str, Any]:
        """At full widths and the cell's 8192 positions, on a seeded
        sequence the window never trained on, against the plain reference
        (``configs/evabyte-6.5b.reference.py`` has the tolerances and their
        reasons), at TWO states — ``trained``: the trainer's own parameters
        as the window left them (what the timed path produced); and
        ``initial``: the parameters the seed gives, made again from the
        seed (``_initial_state`` says what that adds). At each:
        (i) the float32 function (``float32_function`` — on the chip
        through the flash kernels with float32 operands, so the walked pair
        list and the kernels' mask ARE what is compared; off it the einsum
        form): the loss, the eight heads' logits and every gradient leaf;
        (ii) the step as measured (``_step_as_measured``) and the same
        program's forward pass under ``amp``: the loss the step returned,
        the logits, every gradient leaf, and the parameters and second
        moments AdamW leaves.
        At the trained state a leaf whose reference gradient is nought to
        rounding is left out of the gradient comparison, by the
        reference's rule on that gradient (``GRADIENT_FLOOR`` there).
        The trainer is finished by now: its Adam moments are released, and
        each state waits on the host while the other is compared, so that
        the check fits."""
        import jax

        tr = self.trainer
        tr.opt_state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            tr.opt_state)       # shapes stay for ``Trainer.compiled_text``
        ids, labels = self.check_items
        state, tr.state = tr.state, None
        buffers = jax.device_get(state["buffers"])
        with _outside_the_compile_cache():
            programs = self._check_programs()
            out = {"trained": self._compare(
                reference, programs, state, ids, labels, trained=True)}
            del state
            out["initial"] = self._compare(
                reference, programs, self._initial_state(), ids, labels)
        # the trainer gets back what the step took
        tr.state = jax.device_put({"params": out["trained"].pop("params"),
                                   "buffers": buffers})
        del out["initial"]["params"]
        stages = [out[k].pop("stage_s") for k in ("trained", "initial")]
        out["ok"] = all(v["ok"] for v in out.values())
        out["stage_s"] = {k: [s[k] for s in stages] for k in stages[0]}
        out["tokens"] = int(ids.size)
        return out

    def _initial_state(self):
        """The parameters the seed gives, bit for bit those the window
        started from (``__init__`` seeds and builds in this order). What
        this state adds to the trained one: the traffic's ids are
        independent draws, so within ≈ 20 steps at the configured rate the
        model has learnt what there is to learn — the byte frequencies,
        through the heads and the feed-forwards — and the gradient that
        reaches q, k, φ and μ falls to 1e-8 at its largest entry, what is
        left of sums that cancel: those leaves are left out there (the
        reference's ``GRADIENT_FLOOR``) and guarded here, where they are
        1e-5 to 5e-3."""
        import paddle_tpu as pt
        from paddle_tpu import nn
        from paddle_tpu.models import evabyte

        pt.seed(self.seed)
        return nn.get_state(evabyte.EvaByte(_model_cfg(self.cfg)))

    def _compare(self, reference, programs, state, ids, labels,
                 trained: bool = False):
        import jax

        cfg = self.cfg
        took, t = {}, time.perf_counter()

        def lap(name):      # seconds since the last lap, compiles included
            nonlocal t
            took[name] = round(time.perf_counter() - t, 1)
            t = time.perf_counter()

        got = programs["f32"](state, ids, labels)
        lap("f32_function")
        ref = reference.loss_and_grads(state["params"], ids, labels, cfg)
        lap("reference")
        out = {"f32": reference.compare(got, ref, "f32", trained)}
        del got
        # the reference's gradients wait on the host: the step as measured
        # needs the room (3.1 GiB), and the function they are of is the same
        ref["grads"] = jax.device_get(ref["grads"])
        lap("f32_compare")
        # the measured path's logits; its gradients are the step's own
        logits = programs["amp_forward"](state, ids, labels)["logits"]
        got, host_params = self._step_as_measured(reference, programs, state,
                                                  ids, labels)
        got["logits"] = logits
        del state
        lap("step_as_measured")
        out["amp"] = reference.compare(got, ref, "amp", trained)
        out["update"] = got["update"]
        out["ok"] = all(v["ok"] for v in out.values())
        lap("amp_compare")
        out["stage_s"] = took
        out["params"] = host_params
        return out

    def finish(self, flush: bool) -> Dict[str, Any]:
        return {"ok": True}


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> CausalEvaLmSystem:
    return CausalEvaLmSystem(cell, seed, devices, cell.sizes, gen, spans)

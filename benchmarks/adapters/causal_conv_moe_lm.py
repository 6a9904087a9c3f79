"""The system under test for conv-hybrid expert-decoder training:
``executor.Trainer`` driving the repo's ``Lfm2`` one compiled step per
dispatch, fed by the device prefetcher as ``Trainer.train_from_dataset``
feeds it — as ``adapters/causal_mla_moe_lm.py`` drives ``Joyai``, and BY
that file's code wherever it knows nothing of a model: this system is a
``CausalMlaMoeLmSystem`` whose model, loss, bias buffers and check are its
own (the feeder, the dispatch, the window's outcomes, the balance at
set-up, the state check and the form test are inherited; ROADMAP B1 asks
for the base both should stand on).

Program surface held on to (all public): ``paddle_tpu.seed``,
``optimizer.AdamW``, ``nn.functional_call``, ``amp.step_ctx``,
``executor.Trainer`` (``train_step``, ``.state``, ``.opt_state``,
``sync_model``), ``executor.make_train_step``, ``models.lfm2.{Lfm2,
Lfm2Config, lfm2_loss}`` (``forward(ids, output_routing=True)``,
``cfg.attn_impl``, the buffers ``expert_counts``, ``held_assignments``,
``dispatch_rung``, ``tokens_dropped`` and each expert layer's
``expert_bias``), ``parallel.moe.dispatch_ladder``,
``data.prefetcher.device_prefetch``.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from harness import flops_lfm2, spec

_shared = spec.load_module("adapters", "causal_mla_moe_lm")
_outside_the_compile_cache = _shared._outside_the_compile_cache
_records_its_routing = _shared._records_its_routing

_BIAS = "expert_bias"
#: the check's AdamW step runs at the benchmark's OLMoE rate (the window's
#: ``learning_rate`` holds the weights still: ``assumed.optimizer``), so
#: that its update is 2% of a weight and a wrong one cannot hide
CHECK_LEARNING_RATE = 4e-4
#: added, for the check alone, to the held experts' router bias in the
#: first expert layer: scores lie in (0, 1), so every held expert then
#: outranks every absent one and all four of a token's choices land here —
#: 4 T assignments against the buffer's 2 T — so that layer runs the
#: every-expert form while the others keep the buffer and ``correct`` holds
#: BOTH forms to the reference in every run
CHECK_BIAS_PAST_THE_BUFFER = 1.0


def _model_cfg(cfg):
    from paddle_tpu.models.lfm2 import Lfm2Config

    assert cfg["norm_topk_prob"] and cfg["use_expert_bias"] \
        and not cfg["conv_bias"] and cfg["tie_word_embeddings"]
    return Lfm2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        layer_types=tuple(flops_lfm2.layer_kinds(cfg)),
        num_dense_layers=cfg["num_dense_layers"],
        dense_size=cfg["intermediate_size"],
        conv_kernel=cfg["conv_L_cache"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        held=(cfg["held_first"], cfg["num_experts"]),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["norm_eps"],
        bias_update_rate=cfg["bias_update_rate"],
        init_std=cfg["initializer_range"],
        total_layers=cfg["published"]["num_hidden_layers"])


class CausalConvMoeLmSystem(_shared.CausalMlaMoeLmSystem):
    """Inherited as they stand: ``feeder``, ``dispatch``, ``outcomes``
    (what fails a dispatch of the window), ``_balance_router`` (48 annealed
    passes of the program's own bias rule at set-up:
    ``assumed.router_bias``), ``check_state``, ``_attention``,
    ``_ran_every_form``, ``compiled_text``, ``finish``."""

    def __init__(self, cell, seed, devices, sizes, gen, spans) -> None:
        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.executor import Trainer
        from paddle_tpu.models import lfm2    # a program without it fails
        #                                       here, at once

        # the inherited methods read the number of held experts under the
        # other configuration's key
        cfg = self.cfg = dict(cell.config,
                              n_routed_experts=cell.config["num_experts"])
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
        self.check_items = (data["ids"][-1], data["labels"][-1])
        pt.seed(seed)
        self.model = lfm2.Lfm2(_model_cfg(cfg))
        self.loss_fn = lfm2.lfm2_loss
        self.trainer = Trainer(
            self.model,
            optimizer.AdamW(learning_rate=cfg["learning_rate"],
                            weight_decay=cfg["weight_decay"],
                            beta1=cfg["beta1"], beta2=cfg["beta2"],
                            epsilon=cfg["epsilon"]),
            self.loss_fn, amp=cfg["amp"])
        # the Layer now names the trainer's arrays, not a second copy of
        # the parameters (1.9 GiB at full widths)
        self.trainer.sync_model()
        t = time.perf_counter()
        self.balance = self._balance_router()
        spans["balance_s"] = time.perf_counter() - t
        self.load_max_over_mean = None
        self.tokens_dropped = None
        self.held_assignment_share = None
        self.held_assignments_per_dispatch = None
        self.rungs_run = None

    def _bias_names(self) -> List[str]:
        """The router-bias buffers in the order of ``expert_counts``'
        layers."""
        cfg = self.cfg
        return [f"blocks.{i}.moe.{_BIAS}" for i in range(
            cfg["num_dense_layers"], cfg["num_hidden_layers"])]

    def _f32_grads_and_routing(self, state, ids, labels):
        """The float32 function on ``state``, ONE compiled program: the
        gradients of ``lfm2_loss`` through ``nn.functional_call`` with the
        routers' scores and expert index, the biases after the step and
        the rows of the form each layer ran."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import nn

        def both(state, ids, labels):
            def loss_of(params):
                (logits, routes), new = nn.functional_call(
                    self.model, {"params": params,
                                 "buffers": state["buffers"]},
                    ids, output_routing=True, training=True)
                return self.loss_fn(logits, labels), (routes, new["buffers"])

            return jax.value_and_grad(loss_of, has_aux=True)(state["params"])

        (loss, (routes, buffers)), grads = jax.jit(both)(
            state, jnp.asarray(ids), jnp.asarray(labels))
        return dict(self._routing_record(loss, routes, buffers), grads=grads)

    @staticmethod
    def _routing_record(loss, routes, buffers):
        import jax

        routes = jax.device_get(routes)
        return {"loss": float(loss),
                "bias_after": {k: np.asarray(v) for k, v in buffers.items()
                               if k.endswith(_BIAS)},
                "router_scores": 1.0 / (1.0 + np.exp(
                    -routes["logits"].astype(np.float64))),
                "expert_index": routes["index"],
                "rows": np.asarray(buffers["dispatch_rung"]).tolist(),
                "dropped": int(buffers["tokens_dropped"])}

    def _step_as_measured(self, reference, state, ids, labels):
        """What the window runs, on the check's batch: the step that
        ``executor.make_train_step`` builds for ``Trainer`` — the model,
        ``lfm2_loss``, ``amp`` and the attention as configured, AdamW with
        the cell's betas, epsilon and decay — on a whole batch of the
        window's size, every sequence of it DISTINCT (a loss over part of
        the batch is then another loss, with other gradients, bias signs
        and another update; copies of one sequence could not tell) and,
        where the window's rate holds the weights still, at
        ``CHECK_LEARNING_RATE``. From zero moments AdamW's first moment is
        ``(1 - beta1) * gradient``: every gradient leaf is read out of it
        exactly, and the parameters after the step are held to the
        reference's own AdamW on that gradient. The routers' record must
        be THIS program's (in bf16 another program's forward pass flips
        other near-ties), so the model is stepped inside
        ``_RecordsItsRouting``, which leaves it in two buffers. ``state``
        is given up to the step (donated), as the trainer's is."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import optimizer
        from paddle_tpu.executor import make_train_step

        cfg = self.cfg
        assert ids.shape == (self.batch, self.seq)
        hyper = {"lr": CHECK_LEARNING_RATE, "beta1": cfg["beta1"],
                 "beta2": cfg["beta2"], "eps": cfg["epsilon"],
                 "weight_decay": cfg["weight_decay"]}
        opt = optimizer.AdamW(learning_rate=hyper["lr"],
                              weight_decay=hyper["weight_decay"],
                              beta1=hyper["beta1"], beta2=hyper["beta2"],
                              epsilon=hyper["eps"])
        recorder = _records_its_routing(self.model)
        step = make_train_step(recorder, opt, self.loss_fn, donate=True,
                               amp=bool(cfg["amp"]))
        before = jax.device_get(state["params"])    # host: the step takes
        #                                             the device's copy
        inside = lambda tree: {"model." + k: v for k, v in tree.items()}
        params = inside(state["params"])
        new_state, new_opt, loss = step(
            {"params": params, "buffers": inside(state["buffers"])},
            opt.init(params), jax.random.key(0), (jnp.asarray(ids),),
            (jnp.asarray(labels),))
        outside = lambda tree: {k[len("model."):]: v for k, v in tree.items()
                                if k.startswith("model.")}
        buffers = new_state["buffers"]
        got = self._routing_record(
            loss, {"logits": buffers["router_logits"],
                   "index": buffers["expert_index"]}, outside(buffers))
        slots = new_opt["slots"]
        got["update"] = reference.compare_update(
            before, outside(new_state["params"]), outside(slots["m"]),
            outside(slots["v"]), hyper)
        got["grads"] = jax.jit(lambda m: jax.tree_util.tree_map(
            lambda x: x / (1.0 - hyper["beta1"]), m))(outside(slots["m"]))
        return got, before

    def check_reference(self, reference) -> Dict[str, Any]:
        """At full widths, on seeded sequences the window never trained
        on, against the plain reference
        (``configs/lfm2-8b-a1b.reference.py`` has the tolerances and their
        reasons), with the first expert layer's held experts made popular
        enough to leave its buffer (``CHECK_BIAS_PAST_THE_BUFFER``), so
        that both forms of the held dispatch are compared:
        (i) on ONE sequence, the float32 function (``amp`` off, einsum
        attention, matmul precision ``highest``): router scores, top-k
        sets where the k-th and (k+1)-th choice are clear, the loss, every
        gradient leaf and the biases after the step against the
        reference's own routing;
        (ii) on a whole batch of the window's size, every sequence
        distinct, the step as measured (``_step_as_measured``): the share
        of a token's experts that are the reference's own, then the loss
        the step returned, every gradient leaf, the biases after the step
        and the parameters and second moments AdamW leaves, against the
        reference (a sequence at a time, summed) GIVEN the step's own
        expert index.
        The trainer is finished by now: its Adam moments are released
        first, so that the check fits beside the parameters."""
        import jax

        tr = self.trainer
        tr.opt_state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            tr.opt_state)       # shapes stay for ``Trainer.compiled_text``
        ids, labels = self.check_items
        name = self._bias_names()[0]
        first, count = self.cfg["held_first"], self.cfg["num_experts"]
        held_back = jax.device_get(tr.state["buffers"])    # the step as
        #                                 measured takes the device's copy
        buffers = dict(tr.state["buffers"])
        buffers[name] = buffers[name].at[first:first + count].add(
            CHECK_BIAS_PAST_THE_BUFFER)
        state = {"params": tr.state["params"], "buffers": buffers}
        with _outside_the_compile_cache():
            out = self._compare(reference, state, ids, labels)
        # the trainer gets back what the step took
        tr.state = jax.device_put({"params": out.pop("params"),
                                   "buffers": held_back})
        return out

    def _compare(self, reference, state, ids, labels):
        import jax

        cfg = self.cfg
        took, t = {}, time.perf_counter()

        def lap(name):      # seconds since the last lap, compiles included
            nonlocal t
            took[name] = round(time.perf_counter() - t, 1)
            t = time.perf_counter()

        params, buffers = state["params"], jax.device_get(state["buffers"])
        one = ids[:1], labels[:1]
        with self._attention("einsum"), \
                jax.default_matmul_precision("highest"):
            got = self._f32_grads_and_routing(state, *one)
        lap("f32_step")
        ref = reference.loss_and_grads(params, *one, cfg, buffers=buffers)
        lap("reference")
        out = {"f32_routing": reference.compare_routing(got, ref, "f32")}
        flipped = out["f32_routing"]["near_ties_resolved_differently"]
        if flipped:
            # the same function: this reference on the system's choices
            del ref["grads"]
            ref = reference.loss_and_grads(
                params, *one, cfg, buffers=buffers,
                expert_index=got["expert_index"])
            out["f32_routing"] = dict(
                reference.compare_routing(got, ref, "f32"),
                near_ties_resolved_differently=flipped)
        out["f32"] = reference.compare(got, ref, "f32")
        rows = {"f32": got["rows"]}     # which form ran, a layer
        dropped = got["dropped"]
        del ref["grads"], got       # 2 x 1.9 GiB the next step needs
        del params
        lap("f32_compare")
        got, host_params = self._step_as_measured(reference, state, ids,
                                                  labels)
        del state
        lap("step_as_measured")
        ref = reference.loss_and_grads(host_params, ids, labels, cfg,
                                       expert_index=got["expert_index"],
                                       buffers=buffers)
        # the reference's own choice on the hidden states it computed
        out["amp_routing"] = reference.compare_routing(got, ref, "amp")
        out["amp"] = reference.compare(got, ref, "amp")
        out["update"] = got["update"]
        rows["step"] = got["rows"]
        # both forms of the held dispatch must have been compared (where
        # the buffer can overflow at all) and nothing dropped
        forms = {"rows": rows, "dropped": [dropped, got["dropped"]]}
        forms["ok"] = bool(
            self._ran_every_form(rows["f32"], one[0].size)
            and self._ran_every_form(rows["step"], ids.size)
            and forms["dropped"] == [0, 0])
        out["forms"] = forms
        out["ok"] = all(v["ok"] for v in out.values())
        lap("amp_compare")
        out["stage_s"] = took
        out["tokens"] = {"f32": int(one[0].size), "step": int(ids.size)}
        out["params"] = host_params
        return out


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> CausalConvMoeLmSystem:
    return CausalConvMoeLmSystem(cell, seed, devices, cell.sizes, gen, spans)

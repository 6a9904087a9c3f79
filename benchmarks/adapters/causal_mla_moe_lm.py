"""The system under test for latent-attention expert-decoder training:
``executor.Trainer`` driving the repo's ``Joyai`` one compiled step per
dispatch, fed by the device prefetcher as ``Trainer.train_from_dataset``
feeds it.

Program surface held on to (all public): ``paddle_tpu.seed``,
``optimizer.AdamW``, ``nn.functional_call``, ``amp.step_ctx``,
``executor.Trainer`` (``train_step``, ``.state``, ``.opt_state``,
``sync_model``), ``executor.make_train_step``, ``models.joyai.{Joyai, JoyaiConfig, joyai_loss,
joyai_losses, MTP_LOSS_WEIGHT}`` (``forward(ids, output_routing=True)``,
``cfg.attn_impl``, the buffers ``expert_counts``, ``held_assignments``,
``dispatch_rung``, ``tokens_dropped`` and each expert layer's
``e_score_correction_bias``), ``data.prefetcher.device_prefetch``.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import time
from typing import Any, Dict, List

import numpy as np

#: ``Trainer.train_from_dataset(prefetch_depth=2)``
PREFETCH_DEPTH = 2
_BIAS = "e_score_correction_bias"
#: passes of the router-bias rule at set-up, and the rate it is annealed
#: over them, down to the program's own (``assumed.router_bias``)
BALANCE_PASSES = 48
BALANCE_RATE = 0.02
#: the check's AdamW step runs at the benchmark's OLMoE rate (the window's
#: ``learning_rate`` holds the weights still: ``assumed.optimizer``), so
#: that its update is 7% of a weight and a wrong one cannot hide
CHECK_LEARNING_RATE = 4e-4
#: added, for the check alone, to the held experts' router bias in the
#: first expert layer: five of a token's eight choices then land here
#: (scores are 0.5 +- 0.07), past the buffer's twice-the-even-load, so
#: that layer runs the every-expert form while the others keep the buffer
#: and ``correct`` holds BOTH forms to the reference in every run
CHECK_BIAS_PAST_THE_BUFFER = 0.1


def _model_cfg(cfg):
    from paddle_tpu.models.joyai import JoyaiConfig

    assert cfg["qk_head_dim"] == cfg["qk_nope_head_dim"] \
        + cfg["qk_rope_head_dim"]
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"] \
        and cfg["topk_method"] == "noaux_tc" and cfg["rope_interleave"] \
        and cfg["rope_scaling"] is None and cfg["moe_layer_freq"] == 1
    return JoyaiConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        dense_size=cfg["intermediate_size"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        num_shared=cfg["n_shared_experts"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scale=cfg["routed_scaling_factor"],
        held=(cfg["held_first"], cfg["n_routed_experts"]),
        num_mtp=cfg["num_nextn_predict_layers"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        bias_update_rate=cfg["bias_update_rate"],
        init_std=cfg["initializer_range"],
        total_layers=cfg["published"]["num_hidden_layers"])


@contextlib.contextmanager
def _outside_the_compile_cache():
    """The check's three full-width programs run once a run and are large
    (the reference alone is 135 MB on disk, the two gradient programs 54
    MB each: my chip runs, PR 30), and the chip's machine caps the
    persistent cache at 192 MiB for every cell together: written there,
    they evict the train step's, and every run compiles everything anew.
    So they are compiled past the cache and leave it as they found it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _records_its_routing(model):
    """``model`` inside a layer that calls it with ``output_routing`` and
    leaves the routers' logits and expert index in buffers of its own
    (``router_logits``, ``expert_index``): a train step built on it
    (``executor.make_train_step`` returns state, moments and loss, nothing
    else) hands out the routing of the pass it differentiated. Its state
    is the model's under ``model.``."""
    from paddle_tpu import nn

    class _RecordsItsRouting(nn.Layer):
        def __init__(self) -> None:
            super().__init__()
            self.model = model

        def forward(self, ids):
            outputs, routes = self.model(ids, output_routing=True)
            self._buffers["router_logits"] = routes["logits"]
            self._buffers["expert_index"] = routes["index"]
            return outputs

    return _RecordsItsRouting()


class CausalMlaMoeLmSystem:
    unit = "tokens"
    steps_per_dispatch = 1
    table_rows = None

    def __init__(self, cell, seed, devices, sizes, gen, spans) -> None:
        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.executor import Trainer
        from paddle_tpu.models import joyai   # a program without it fails
        #                                       here, at once

        cfg = self.cfg = cell.config
        assert cfg["mtp_loss_weight"] == joyai.MTP_LOSS_WEIGHT
        self.seed, self.spans = seed, spans
        self.seq = int(cell.traffic["seq_len"])
        assert self.seq <= cfg["max_position_embeddings"]
        B = self.batch = sizes["batch_per_chip"] * len(devices)
        self.units_per_dispatch = B * self.seq
        # one batch more than the window cycles: the check's sequences,
        # drawn like the traffic and never trained on
        data = gen.generate(cell.traffic, seed, vocab=cfg["vocab_size"],
                            batches=sizes["host_dispatches"] + 1, batch=B)
        self.host_items = list(zip(data["ids"][:-1], data["labels"][:-1]))
        n = sizes["check_sequences"]
        assert n <= B
        self.check_items = (data["ids"][-1][:n], data["labels"][-1][:n])
        pt.seed(seed)
        self.model = joyai.Joyai(_model_cfg(cfg))
        self.trainer = Trainer(
            self.model,
            optimizer.AdamW(learning_rate=cfg["learning_rate"],
                            weight_decay=cfg["weight_decay"],
                            beta1=cfg["beta1"], beta2=cfg["beta2"],
                            epsilon=cfg["epsilon"]),
            joyai.joyai_loss, amp=cfg["amp"])
        # the Layer now names the trainer's arrays, not a second copy of
        # the parameters (2.5 GiB at full widths)
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
        layers: the expert layers, then the prediction module's."""
        cfg = self.cfg
        return [f"blocks.{i}.moe.{_BIAS}" for i in range(
            cfg["first_k_dense_replace"], cfg["num_hidden_layers"])] \
            + [f"mtp.block.moe.{_BIAS}"]

    def _balance_router(self) -> Dict[str, Any]:
        """The router biases as a job past its first steps has them. At
        step 0 every token's hidden state is nearly the same vector
        (embeddings of 0.006 under an attention output that averages the
        prefix), so each layer sends ALL tokens to a few experts (busiest /
        mean 21-32 of a possible 32) and whether those are among the 16
        held is the seed's luck: the held share read 0.03 to 0.09 and the
        rate followed it by 5% (my chip runs, PR 30). The rule that cures
        this in training — ``b += rate * sign(mean(c) - c)`` on the
        step's own counts — moves 0.001 a step against score gaps of
        0.06: hundreds of steps. Set-up runs that same rule on the first
        batch's FORWARD pass (the step's own, ``amp`` as configured),
        ``BALANCE_PASSES`` times with the rate annealed from
        ``BALANCE_RATE`` to the program's, in one compiled loop, weights
        untouched; the window then starts from balanced loads, which the
        step's own rule keeps. Returns what it reached."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from paddle_tpu import nn
        from paddle_tpu.amp import step_ctx

        cfg, tr, names = self.cfg, self.trainer, self._bias_names()
        ids = jnp.asarray(self.host_items[0][0])
        last = max(BALANCE_PASSES - 1, 1)
        decay = (cfg["bias_update_rate"] / BALANCE_RATE) ** (1.0 / last)

        def counts_of(state, biases, ids):
            buffers = dict(state["buffers"], **dict(zip(names, biases)))
            with step_ctx(bool(cfg["amp"])):
                _, new = nn.functional_call(
                    self.model, {"params": state["params"],
                                 "buffers": buffers}, ids, training=True)
            return new["buffers"]["expert_counts"].astype(jnp.float32)

        def run(state, ids):    # ids an argument: one program for every seed
            def one_pass(i, biases):
                counts = counts_of(state, biases, ids)
                rate = BALANCE_RATE * decay ** i.astype(jnp.float32)
                return [b + rate * jnp.sign(jnp.mean(c) - c)
                        for b, c in zip(biases, counts)]

            biases = lax.fori_loop(0, BALANCE_PASSES, one_pass,
                                   [state["buffers"][n] for n in names])
            return biases, counts_of(state, biases, ids)

        biases, counts = jax.jit(run)(tr.state, ids)
        tr.state["buffers"].update(zip(names, biases))
        counts = np.asarray(counts)
        first, n = cfg["held_first"], cfg["n_routed_experts"]
        out = {"passes": BALANCE_PASSES,
               "load_max_over_mean": float(np.max(
                   counts.max(axis=1) / counts.mean(axis=1))),
               "held_share": float(counts[:, first:first + n].sum()
                                   / counts.sum())}
        print(json.dumps({"router_balance": out}), flush=True)
        return out

    def feeder(self):
        from paddle_tpu.data.prefetcher import device_prefetch

        return device_prefetch(itertools.cycle(self.host_items),
                               depth=PREFETCH_DEPTH)

    def dispatch(self, item):
        import jax.numpy as jnp

        loss = self.trainer.train_step(item[0], item[1])
        # the step's counters, copied: the next step is given (donated)
        # the buffers they live in
        b = self.trainer.state["buffers"]
        return (loss, jnp.copy(b["expert_counts"]),
                jnp.copy(b["tokens_dropped"]),
                jnp.copy(b["held_assignments"]),
                jnp.copy(b["dispatch_rung"]))

    def outcomes(self, handles):
        """(dispatches that failed, the loss of each dispatch). A dispatch
        fails on a loss that is not finite, a held assignment not
        computed, expert counts that do not sum to tokens x experts a
        token in every expert layer, held assignments that disagree with
        the counts of the held experts, or more of them than the rung
        that ran has rows."""
        import jax

        cfg = self.cfg
        want = self.units_per_dispatch * cfg["num_experts_per_tok"]
        first, count = cfg["held_first"], cfg["n_routed_experts"]
        losses, loads, held_all, rungs = [], [], [], {}
        failed = dropped = 0
        for loss, counts, drop, held, rung in jax.device_get(handles):
            losses.append(float(loss))
            dropped += int(drop)
            loads.append(float(np.max(counts.max(axis=1)
                                      / counts.mean(axis=1))))
            held_all.append(held.sum())
            for r in rung.tolist():
                rungs[r] = rungs.get(r, 0) + 1
            failed += int(
                not np.isfinite(loss) or int(drop) != 0
                or not (counts.sum(axis=1) == want).all()
                or not (counts[:, first:first + count].sum(axis=1)
                        == held).all()
                or not (held <= rung).all())
        layers = len(handles[0][3]) if handles else 1
        self.tokens_dropped = dropped
        self.load_max_over_mean = float(np.mean(loads))
        self.held_assignments_per_dispatch = float(np.mean(held_all))
        self.held_assignment_share = float(np.mean(held_all)) / (want * layers)
        self.rungs_run = rungs
        print(json.dumps({"dispatch_rungs": rungs, "held_assignment_share":
                          self.held_assignment_share}), flush=True)
        return failed, losses

    def compiled_text(self) -> str:
        return ""        # harness/scopes.py asks the trainer itself

    def check_state(self) -> Dict[str, Any]:
        """Parameters and router biases after the window are finite."""
        import jax
        import jax.numpy as jnp

        state = self.trainer.state
        floats = [x for x in jax.tree_util.tree_leaves(state)
                  if jnp.issubdtype(x.dtype, jnp.floating)]
        ok = bool(jax.jit(lambda t: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in t])))(floats))
        return {"ok": ok, "steps_counted": int(self.trainer.global_step)}

    @contextlib.contextmanager
    def _attention(self, impl: str):
        was = self.model.cfg.attn_impl
        self.model.cfg.attn_impl = impl
        try:
            yield
        finally:
            self.model.cfg.attn_impl = was

    def _f32_grads_and_routing(self, state, ids, labels):
        """The float32 function on ``state``, ONE compiled program: the
        gradients of ``joyai_loss`` through ``nn.functional_call`` with
        both losses, the routers' scores and expert index, the biases
        after the step and the rows of the form each layer ran."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import nn
        from paddle_tpu.models.joyai import MTP_LOSS_WEIGHT, joyai_losses

        def both(state, ids, labels):
            def total(params):
                (outputs, routes), new = nn.functional_call(
                    self.model, {"params": params,
                                 "buffers": state["buffers"]},
                    ids, output_routing=True, training=True)
                main, mtp = joyai_losses(outputs, labels)
                return main + MTP_LOSS_WEIGHT * mtp, (
                    main, mtp, routes, new["buffers"])

            return jax.value_and_grad(total, has_aux=True)(state["params"])

        (total, (main, mtp, routes, buffers)), grads = jax.jit(both)(
            state, jnp.asarray(ids), jnp.asarray(labels))
        return dict(self._routing_record(total, routes, buffers),
                    loss=float(main), loss_mtp=float(mtp), grads=grads)

    @staticmethod
    def _routing_record(total, routes, buffers):
        import jax

        routes = jax.device_get(routes)
        return {"total": float(total),
                "bias_after": {k: np.asarray(v) for k, v in buffers.items()
                               if k.endswith(_BIAS)},
                "router_scores": 1.0 / (1.0 + np.exp(
                    -routes["logits"].astype(np.float64))),
                "expert_index": routes["index"],
                "rows": np.asarray(buffers["dispatch_rung"]).tolist(),
                "dropped": int(buffers["tokens_dropped"])}

    def _step_as_measured(self, reference, state, ids, labels):
        """What the window runs, on the check's sequences: the step that
        ``executor.make_train_step`` builds for ``Trainer`` — the model,
        ``joyai_loss``, ``amp`` and the attention as configured, AdamW
        with the cell's betas, epsilon and decay — at the window's batch
        (the check's sequences repeated to fill it: the mean loss, its
        gradients and the sign the bias rule reads are those of one copy)
        and, where the window's rate holds the weights still, at
        ``CHECK_LEARNING_RATE``. From zero moments AdamW's first moment is
        ``(1 - beta1) * gradient``: every gradient leaf is read out of it
        exactly, and the parameters after the step are held to the
        reference's own AdamW on that gradient. The routers' record must
        be THIS program's (in bf16 another program's forward pass flips
        other near-ties: my chip run, PR 30), so the model is stepped
        inside ``_RecordsItsRouting``, which leaves it in two buffers.
        ``state`` is given up to the step (donated), as the trainer's
        is."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import optimizer
        from paddle_tpu.executor import make_train_step
        from paddle_tpu.models.joyai import joyai_loss

        cfg = self.cfg
        reps = self.batch // ids.shape[0]
        assert reps * ids.shape[0] == self.batch
        ids_w = jnp.asarray(np.tile(ids, (reps, 1)))
        labels_w = jnp.asarray(np.tile(labels, (reps, 1)))
        hyper = {"lr": CHECK_LEARNING_RATE, "beta1": cfg["beta1"],
                 "beta2": cfg["beta2"], "eps": cfg["epsilon"],
                 "weight_decay": cfg["weight_decay"]}
        opt = optimizer.AdamW(learning_rate=hyper["lr"],
                              weight_decay=hyper["weight_decay"],
                              beta1=hyper["beta1"], beta2=hyper["beta2"],
                              epsilon=hyper["eps"])
        recorder = _records_its_routing(self.model)
        step = make_train_step(recorder, opt, joyai_loss, donate=True,
                               amp=bool(cfg["amp"]))
        before = jax.device_get(state["params"])    # host: the step takes
        #                                             the device's copy
        inside = lambda tree: {"model." + k: v for k, v in tree.items()}
        params = inside(state["params"])
        new_state, new_opt, total = step(
            {"params": params, "buffers": inside(state["buffers"])},
            opt.init(params), jax.random.key(0), (ids_w,), (labels_w,))
        outside = lambda tree: {k[len("model."):]: v for k, v in tree.items()
                                if k.startswith("model.")}
        buffers = new_state["buffers"]
        got = self._routing_record(
            total, {"logits": buffers["router_logits"],
                    "index": buffers["expert_index"]}, outside(buffers))
        # the copies of a sequence must have been routed alike
        n = ids.size
        index = got["expert_index"].reshape(
            got["expert_index"].shape[0], reps, n, -1)
        got["copies_routed_alike"] = bool((index == index[:, :1]).all())
        got["expert_index"] = index[:, 0]
        got["router_scores"] = got["router_scores"][:, :n]
        slots = new_opt["slots"]
        got["update"] = reference.compare_update(
            before, outside(new_state["params"]), outside(slots["m"]),
            outside(slots["v"]), hyper)
        got["grads"] = jax.jit(lambda m: jax.tree_util.tree_map(
            lambda x: x / (1.0 - hyper["beta1"]), m))(outside(slots["m"]))
        return got, before

    def check_reference(self, reference) -> Dict[str, Any]:
        """On one seeded sequence at full widths, against the plain
        reference (``configs/joyai-llm-flash.reference.py`` has the
        tolerances and their reasons), with the first expert layer's held
        experts made popular enough to leave its buffer
        (``CHECK_BIAS_PAST_THE_BUFFER``), so that both forms of the held
        dispatch are compared:
        (a) the float32 function (``amp`` off, einsum attention, matmul
        precision ``highest``): router scores, top-k sets where the k-th
        and (k+1)-th choice are clear, both losses, every gradient leaf
        and the biases after the step against the reference's own routing;
        (b) the step as measured (``_step_as_measured``): the share of a
        token's experts that are the reference's own, then the loss the
        step returned, every gradient leaf, the biases after the step and
        the parameters AdamW leaves, against the reference GIVEN the
        step's own expert index.
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
        first, count = self.cfg["held_first"], self.cfg["n_routed_experts"]
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
        with self._attention("einsum"), \
                jax.default_matmul_precision("highest"):
            got = self._f32_grads_and_routing(state, ids, labels)
        lap("f32_step")
        ref = reference.loss_and_grads(params, ids, labels, cfg,
                                       buffers=buffers)
        lap("reference")
        own_index = ref["own_index"]
        out = {"f32_routing": reference.compare_routing(got, ref, "f32")}
        flipped = out["f32_routing"]["near_ties_resolved_differently"]
        if flipped:
            # the same function: this reference on the system's choices
            # (its own choice and gaps are then those of the hidden states
            # the system's choices lead to)
            del ref["grads"]
            ref = reference.loss_and_grads(
                params, ids, labels, cfg, buffers=buffers,
                expert_index=got["expert_index"])
            out["f32_routing"] = dict(
                reference.compare_routing(got, ref, "f32"),
                near_ties_resolved_differently=flipped)
        out["f32"] = reference.compare(got, ref, "f32")
        rows = {"f32": got["rows"]}     # which form ran, a layer
        dropped = got["dropped"]
        del ref["grads"], got       # 2 x 2.5 GiB the next step needs
        del params
        lap("f32_compare")
        got, host_params = self._step_as_measured(reference, state, ids,
                                                  labels)
        del state
        lap("step_as_measured")
        out["amp_routing"] = reference.compare_routing(
            got, {"own_index": own_index}, "amp")
        ref = reference.loss_and_grads(host_params, ids, labels, cfg,
                                       expert_index=got["expert_index"],
                                       buffers=buffers)
        out["amp"] = reference.compare(got, ref, "amp")
        out["update"] = got["update"]
        rows["step"] = got["rows"]
        # both forms of the held dispatch must have been compared (where
        # the buffer can overflow at all) and nothing dropped
        forms = {"rows": rows,
                 "copies_routed_alike": got["copies_routed_alike"],
                 "dropped": [dropped, got["dropped"]]}
        forms["ok"] = bool(
            self._ran_every_form(rows["f32"], ids.size)
            and self._ran_every_form(rows["step"], self.units_per_dispatch)
            and forms["dropped"] == [0, 0]
            and forms["copies_routed_alike"])
        out["forms"] = forms
        out["ok"] = all(v["ok"] for v in out.values())
        lap("amp_compare")
        out["stage_s"] = took
        out["tokens"] = int(ids.size)
        out["params"] = host_params
        return out

    def _ran_every_form(self, rows: List[int], tokens: int) -> bool:
        from paddle_tpu.parallel.moe import dispatch_ladder

        cfg = self.cfg
        k = cfg["num_experts_per_tok"]
        buffer, dense = dispatch_ladder(tokens, k, cfg["router_width"],
                                        cfg["n_routed_experts"])
        want = {buffer} if buffer == tokens * k else {buffer, dense}
        return want <= set(rows)

    def finish(self, flush: bool) -> Dict[str, Any]:
        return {"ok": True}


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> CausalMlaMoeLmSystem:
    return CausalMlaMoeLmSystem(cell, seed, devices, cell.sizes, gen, spans)

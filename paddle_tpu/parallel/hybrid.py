"""Hybrid parallelism: one compiled SPMD step over a dp×pp×cp×mp(×sh) mesh.

The reference composes its four-way hybrid (dp, pp, sharding, mp) out of
separate mechanisms — ``HybridCommunicateGroup`` builds comm groups
(fleet/base/topology.py:133), ``HybridParallelOptimizer`` wraps the inner
optimizer, meta-optimizers rewrite programs per axis, and at runtime each
axis runs its own NCCL rings. TPU-native inversion: the whole hybrid step
is ONE shard_map'd, jitted program over a named mesh; XLA schedules every
axis's collectives together and overlaps them with compute on ICI.

Axes (superset of the reference's, adding cp/ep — SURVEY §2.6):
  dp  batch;        pp  pipeline stages (compiled 1F-then-B schedule,
  see parallel.pipeline);  cp  sequence shard (ring attention);
  mp  tensor parallel;  sh  sharding/ZeRO (below).  ep rides dp (the
  standard MoE deployment: expert shards exchange tokens across the
  data-parallel group).

``sh`` is the reference's 4th hybrid axis — the *sharding* group of
``topology.py:133`` / ``sharding_optimizer.py``: an inner data-parallel
group (the batch splits over dp×sh) whose ranks additionally partition
the optimizer state. Params and grads stay at global shapes in the
step; every optimizer SLOT leaf is device-sharded over "sh" on its
first free divisible dim (composing with the pp chunk-stacking dim and
any mp dims already in the param's spec), so the update compute and
slot memory scale 1/sh and XLA inserts the param all-gather the
reference's sharding-stage-1 broadcast does. Checkpoints stay
layout-independent (global shapes), so a snapshot restores across
different sh factorizations.

Gradient synchronization (replaces the reference's Reducer / c_allreduce
insertion): none is written by hand. shard_map's varying-manual-axes type
system transposes the implicit broadcast of every replicated parameter
into a psum over exactly the axes it was replicated on (verified: jax
0.9 returns full-batch grads for P()-spec params), so each grad leaf
comes back with its parameter's own layout — dp/sh/cp batch reduction,
pp masking for embed/head, and per-shard mp/ep grads all fall out of
autodiff.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..ops import collectives as coll
from ..models.ernie import (Ernie, ErnieConfig, ErnieEmbedding, ErnieHead,
                            ErnieStage, parallel_cross_entropy, partition_spec)
from .pipeline import pipeline_spmd_fn

__all__ = ["HybridParallelTrainer"]

PyTree = Any


def _spec_tree(state: PyTree, cfg: ErnieConfig, leading_pp: bool) -> PyTree:
    # tree_map preserves the exact pytree node types (OrderedDicts from
    # nn.get_state), which shard_map's in_specs prefix matching requires
    return jax.tree_util.tree_map_with_path(
        lambda path, a: partition_spec(path[-1].key, a, cfg, leading_pp=leading_pp),
        state)


def _insert_sh(spec: P, shape: Tuple[int, ...], sh: int) -> P:
    """Add the "sh" axis to a param's PartitionSpec on the first free dim
    divisible by the sharding degree (sharding_optimizer.py's param→rank
    assignment, expressed as one more mesh dim in the slot's layout).
    Leaves with no divisible free dim stay replicated over sh — the same
    remainder the reference leaves on every rank."""
    spec_t = tuple(spec) + (None,) * (len(shape) - len(spec))
    for i, (ax, d) in enumerate(zip(spec_t, shape)):
        if ax is None and d and d % sh == 0:
            return P(*spec_t[:i], "sh", *spec_t[i + 1:])
    return P(*spec_t)


class HybridParallelTrainer:
    """dp×pp×cp×mp training of an Ernie-family model in one jitted step.

    Parameters are kept at GLOBAL shapes on host-visible sharded arrays;
    shard_map in_specs (from models.ernie.partition_spec) hand each rank
    its local shard, so checkpoints are layout-independent.
    """

    def __init__(
        self,
        cfg: ErnieConfig,
        mesh: Mesh,
        optimizer,
        num_micro: int = 2,
        seed: int = 0,
    ) -> None:
        for ax in ("dp", "pp", "cp", "mp"):
            enforce(ax in mesh.shape, f"hybrid mesh lacks axis {ax!r}")
        # optional 5th axis: the sharding/ZeRO group (topology.py:133's
        # 4th); an inner dp group whose ranks partition the opt state
        self.sh = int(mesh.shape.get("sh", 1))
        pp = mesh.shape["pp"]
        enforce_eq(cfg.num_layers % pp, 0, "num_layers must divide pp")
        if cfg.num_experts:
            # ep rides dp: MoE all-to-all crosses the data-parallel group
            cfg = dataclasses.replace(cfg, ep_axis="dp")
        self.cfg = cfg
        self.mesh = mesh
        self.num_micro = num_micro
        self.optimizer = optimizer

        blocks_per_stage = cfg.num_layers // pp
        self._stage_tmpl = ErnieStage(cfg, blocks_per_stage)
        self._embed_tmpl = ErnieEmbedding(cfg)
        self._head_tmpl = ErnieHead(cfg)
        stages = [nn.get_state(ErnieStage(cfg, blocks_per_stage)) for _ in range(pp)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stages)
        aux = {"embed": nn.get_state(self._embed_tmpl),
               "head": nn.get_state(self._head_tmpl)}
        self.params = {"stages": stacked, "aux": aux}

        stage_specs = _spec_tree(stacked, cfg, leading_pp=True)
        aux_specs = {k: _spec_tree(v, cfg, leading_pp=False) for k, v in aux.items()}
        self._param_specs = {"stages": stage_specs, "aux": aux_specs}

        # multi-HOST: the mesh spans processes, so params/batches must be
        # GLOBAL jax.Arrays (each host holds identical full values — the
        # same seed built them — and contributes its local shards)
        self._multihost = jax.process_count() > 1
        if self._multihost:
            from jax.sharding import NamedSharding

            self.params = jax.tree_util.tree_map(
                self._globalize, self.params, self._param_specs)
            # ONE cached compiled identity re-lays-out pytrees replicated
            # for checkpointing (jit caches per tree structure: params
            # and opt state each compile once across all saves)
            self._gather = jax.jit(
                lambda t: t, out_shardings=NamedSharding(mesh, P()))
            # init under jit: eager zeros_like on non-addressable global
            # arrays is not computable host-side
            self.opt_state = jax.jit(optimizer.init)(self.params)
        else:
            self.opt_state = optimizer.init(self.params)

        def stage_apply(state, x):
            out, _ = nn.functional_call(self._stage_tmpl, state, x, training=True)
            return out

        def embed_apply(state, x):
            out, _ = nn.functional_call(self._embed_tmpl, state, x, training=True)
            return out

        def head_apply(state, y):
            out, _ = nn.functional_call(self._head_tmpl, state, y, training=True)
            return out

        pipe = pipeline_spmd_fn(stage_apply, pp, num_micro, "pp",
                                embed_apply, head_apply)

        dp_n, cp_n = mesh.shape["dp"], mesh.shape["cp"]
        # the sharding group is an inner data-parallel group: the batch
        # splits over dp×sh and the loss reduces over both
        batch_axes = ("dp", "sh") if self.sh > 1 else ("dp",)
        batch_n = dp_n * (self.sh if self.sh > 1 else 1)
        # mp=1 takes the serial CE path (no mp psum), so mark the loss
        # replicated over mp with an identity psum or the out_specs=P()
        # vma check rejects the program
        mp_extra = ("mp",) if mesh.shape["mp"] == 1 else ()

        def spmd_loss(params, ids_micro, labels_micro, rng):
            key = jax.random.fold_in(rng, lax.axis_index("pp"))
            with nn.rng_guard(key):
                logits = pipe(params["stages"], params["aux"], ids_micro)
            # pinned_vjp: this step runs check_vma=False with every
            # reduction explicit — see parallel_cross_entropy's docstring
            ce = parallel_cross_entropy(logits, labels_micro, cfg.vocab_size,
                                        cfg.mp_axis, pinned_vjp=True)
            local = jnp.mean(ce)
            # mean over the (dp×sh)×cp token grid (equal shard sizes).
            # The loss psum is DIFFERENTIATED (value_and_grad below) and
            # its cotangent is replicated over these axes, so it must be
            # the identity-VJP psum: under check_vma=False a plain psum
            # transposes into another psum, scaling every grad by the
            # axis-size product.
            return coll.psum_replicated(local / (batch_n * cp_n),
                                        batch_axes + ("cp",) + mp_extra)

        mesh_shape = dict(mesh.shape)

        def spmd_step(params, ids_micro, labels_micro, rng):
            loss, grads = jax.value_and_grad(spmd_loss)(params, ids_micro,
                                                        labels_micro, rng)
            # explicit spec-driven reductions (the pipeline-trainer
            # treatment from PR 2): check_rep=False + pinned-VJP psums
            # keep every cotangent PARTIAL per rank, so each param
            # psums over exactly the axes it is replicated on — see
            # coll.spec_reduced_grads
            grads = coll.spec_reduced_grads(grads, self._param_specs,
                                            mesh_shape)
            return loss, grads

        # ids/labels: [num_micro, B_local, L_local] → batch over dp(×sh),
        # seq over cp
        data_spec = P(None, batch_axes, "cp")
        self._data_spec = data_spec
        # check_vma=False: every reduction in this step is EXPLICIT
        # (identity-VJP psums in the loss, the pipe's masked psum and
        # the PCE internals, then spec_reduced_grads) — oracle:
        # test_hybrid_grads_match_serial
        grad_fn = shard_map(
            spmd_step,
            mesh=mesh,
            in_specs=(self._param_specs, data_spec, data_spec, P()),
            out_specs=(P(), self._param_specs),
            check_vma=False,
        )

        # ZeRO: shard every optimizer slot leaf over "sh" (params/grads
        # stay global — XLA slices the update and all-gathers new params,
        # the compiled form of sharding_optimizer's update+broadcast)
        self._opt_shardings = None
        if self.sh > 1:
            from jax.sharding import NamedSharding

            opt_specs = self._opt_spec_tree()
            self._opt_shardings = jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec), opt_specs,
                is_leaf=lambda x: isinstance(x, P))
            self.opt_state = jax.tree_util.tree_map(
                jax.device_put, self.opt_state, self._opt_shardings)

        def step(params, opt_state, ids_micro, labels_micro, rng):
            loss, grads = grad_fn(params, ids_micro, labels_micro, rng)
            new_params, new_opt = optimizer.update(grads, opt_state, params)
            if self._opt_shardings is not None:
                new_opt = jax.tree_util.tree_map(
                    lax.with_sharding_constraint, new_opt,
                    self._opt_shardings)
            return new_params, new_opt, loss

        # PIN carried-state shardings on the step (the Engine treatment
        # from PR 2): without them the first call compiles against
        # uncommitted inputs while later calls compile against whatever
        # output layout GSPMD chose, and those two executables were
        # seen to COMPUTE DIFFERENT VALUES (the steady-state one
        # disagreed with the serial forward oracle by ~5%, which is what
        # actually failed test_hybrid_save_load_resume — a resumed
        # trainer starts on the fresh executable while the donor
        # continued on the drifted one). One pinned layout ⇒ one
        # executable ⇒ save/load and cross-mesh parity are exact.
        from jax.sharding import NamedSharding

        ns = lambda spec: NamedSharding(mesh, spec)
        param_shardings = jax.tree_util.tree_map(
            ns, self._param_specs, is_leaf=lambda x: isinstance(x, P))
        opt_shardings = (self._opt_shardings if self._opt_shardings is not None
                         else jax.tree_util.tree_map(lambda _: ns(P()),
                                                     self.opt_state))
        if self._multihost:
            # opt state came out of jit(init) with GSPMD-chosen layouts;
            # re-place it to match the pinned step signature
            self.opt_state = jax.tree_util.tree_map(
                jax.device_put, self.opt_state, opt_shardings)
        data_sh = ns(self._data_spec)
        self._step = jax.jit(
            step,
            in_shardings=(param_shardings, opt_shardings, data_sh, data_sh,
                          ns(P())),
            out_shardings=(param_shardings, opt_shardings, ns(P())),
            donate_argnums=(0, 1))
        self._rng = jax.random.key(seed)
        self.global_step = 0

    def _globalize(self, x, spec):
        """Host value (identical on every process) → global jax.Array
        sharded per ``spec`` over the trainer's mesh."""
        from jax.sharding import NamedSharding

        arr = np.asarray(x)
        sh = NamedSharding(self.mesh, spec if isinstance(spec, P) else P())
        return jax.make_array_from_callback(arr.shape, sh,
                                            lambda idx: arr[idx])

    def _opt_spec_tree(self):
        """PartitionSpecs for the optimizer state: slot subtrees that
        mirror the params tree get each param's spec with "sh" inserted
        (:func:`_insert_sh`); anything else (step counter, scalar
        schedule state) replicates."""
        from ..optimizer import map_param_slots

        pspecs = self._param_specs
        slots = map_param_slots(
            self.opt_state["slots"], self.params,
            mirror_fn=lambda sub: jax.tree_util.tree_map(
                lambda spec, leaf: _insert_sh(spec, leaf.shape, self.sh),
                pspecs, sub),
            other_leaf_fn=lambda _: P())
        return {"step": P(), "slots": slots}

    def save(self, path: str) -> None:
        """Persist params + optimizer state + rng + step (the shared
        trainer-snapshot schema; layout-independent — params live at
        GLOBAL shapes, so a checkpoint written on one mesh restores
        onto any other). Multi-host: sharded leaves are re-laid-out
        replicated (one compiled identity) so every process can read the
        full values; process 0 writes."""
        from ..io.checkpoint import save_train_state

        params, opt = self.params, self.opt_state
        if self._multihost:
            params, opt = self._gather(params), self._gather(opt)
            if jax.process_index() != 0:
                return
        save_train_state(path, params, opt_state=opt,
                         rng=self._rng, step=self.global_step)

    def load(self, path: str) -> None:
        """Restore a snapshot saved by :meth:`save`; resumed training
        continues the same step count and rng stream. Values restore
        INTO the live pytrees by key path — loaded containers are plain
        dicts while shard_map's in_specs were built from the OrderedDict
        state trees — and each leaf is device_put with its current
        leaf's sharding so the compiled step's cache stays valid (a
        wholesale swap to uncommitted arrays would trigger a second
        full compile)."""
        from ..io.checkpoint import graft_into, load_train_state

        snap = load_train_state(path)
        self.params = graft_into(self.params, snap["state"])
        self.opt_state = graft_into(self.opt_state, snap["opt"])
        if snap["rng"] is not None:
            self._rng = snap["rng"]
        self.global_step = snap["step"]

    def train_step(self, ids, labels):
        """ids/labels: [batch, seq] global arrays; batch must divide
        num_micro (micro-batching) — dp/cp sharding happens via GSPMD."""
        B = ids.shape[0]
        enforce_eq(B % self.num_micro, 0, "batch must divide num_micro")
        m = self.num_micro
        self._rng, sub = jax.random.split(self._rng)
        if self._multihost:
            # every process feeds the SAME host batch; shard it into one
            # global array per the data spec (the mesh spans processes)
            ids_m = self._globalize(
                np.asarray(ids).reshape(m, B // m, *ids.shape[1:]),
                self._data_spec)
            labels_m = self._globalize(
                np.asarray(labels).reshape(m, B // m, *labels.shape[1:]),
                self._data_spec)
            sub = jax.random.wrap_key_data(
                self._globalize(jax.random.key_data(sub), P()))
        else:
            # single-host: reshape stays wherever the caller's arrays
            # live (no forced device→host copy on the hot path)
            ids_m = ids.reshape(m, B // m, *ids.shape[1:])
            labels_m = labels.reshape(m, B // m, *labels.shape[1:])
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, ids_m, labels_m, sub)
        self.global_step += 1
        return loss

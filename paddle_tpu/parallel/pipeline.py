"""Pipeline parallelism.

Replaces three reference mechanisms (SURVEY §2.6 PP row):
- static ``pipeline_optimizer`` + ``SectionWorker`` schedulers
  (framework/section_worker.cc:92-189, F-then-B and 1F1B),
- the FleetExecutor interceptor runtime (compute_interceptor.cc) whose
  credit-based message passing sequences micro-batches across ranks,
- dygraph ``PipelineParallel`` + p2p_communication.py.

TPU-native inversion: instead of an actor runtime exchanging activations
via RPC, the schedule is *compiled*. Stages live on the ``pp`` mesh axis
(shard_map); micro-batches advance through a ``lax.scan`` whose body runs
the local stage and rotates activations one hop with ``ppermute`` (the
partial_send/recv pair). Autodiff through scan+ppermute yields the reverse
(backward) pipeline automatically — the transpose of a rotation is the
opposite rotation — so fwd+bwd is the F-then-B schedule with XLA
overlapping compute and ICI transfers. Bubble fraction matches the classic
(S-1)/(M+S-1).

Stages must be structurally identical (transformer-block style); per-stage
parameters are stacked on a leading axis sharded over ``pp``. First/last
ranks additionally apply embed/head params (replicated; their compute is
masked out elsewhere).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..nn.layer import Layer
from ..ops import collectives as coll

__all__ = ["LayerDesc", "PipelineLayer", "pipeline_spmd_fn", "PipelineTrainer"]

PyTree = Any


class LayerDesc:
    """Deferred layer construction (pp_layers.py LayerDesc): lets each pp
    rank materialize only its own stages."""

    def __init__(self, layer_cls, *args, **kwargs) -> None:
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build(self) -> Layer:
        return self.layer_cls(*self.args, **self.kwargs)


def _stack_states(states: Sequence[dict]) -> dict:
    """Stack per-stage state pytrees along a new leading axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


class PipelineLayer(Layer):
    """Container of S structurally identical stages plus optional
    embed/head layers (pp_layers.py PipelineLayer analogue)."""

    def __init__(
        self,
        stage_descs: Sequence[LayerDesc],
        embed: Optional[Layer] = None,
        head: Optional[Layer] = None,
    ) -> None:
        super().__init__()
        self.num_stages = len(stage_descs)
        self.stages = nn.LayerList([d.build() for d in stage_descs])
        if embed is not None:
            self.embed = embed
        if head is not None:
            self.head = head

    def stage_stacked_state(self) -> dict:
        return _stack_states([nn.get_state(s) for s in self.stages])

    def aux_state(self) -> dict:
        out = {}
        if "embed" in self._sub_layers:
            out["embed"] = nn.get_state(self._sub_layers["embed"])
        if "head" in self._sub_layers:
            out["head"] = nn.get_state(self._sub_layers["head"])
        return out

    def forward(self, x):  # serial reference path (for parity tests)
        if "embed" in self._sub_layers:
            x = self._sub_layers["embed"](x)
        for s in self.stages:
            x = s(x)
        if "head" in self._sub_layers:
            x = self._sub_layers["head"](x)
        return x


def pipeline_spmd_fn(
    stage_apply: Callable[[PyTree, jax.Array], jax.Array],
    num_stages: int,
    num_micro: int,
    pp_axis: str = "pp",
    embed_apply: Optional[Callable[[PyTree, jax.Array], jax.Array]] = None,
    head_apply: Optional[Callable[[PyTree, jax.Array], jax.Array]] = None,
):
    """Build the per-rank SPMD pipeline function.

    Returns ``fn(stacked_stage_state, aux_state, x_micro) -> y_micro``
    to be called inside shard_map with ``stacked_stage_state`` sharded on
    the pp axis (leading dim) and ``x_micro`` of shape
    ``[num_micro, micro_batch, ...]`` — identical across pp ranks;
    callers may shard the micro_batch dim over a dp axis (the trainer
    does), in which case each rank pipelines its own batch shard.
    Output is the last stage's head output per micro-batch (same
    dp-sharding as the input), replicated over pp via psum masking.
    """

    def fn(stacked_state, aux_state, x_micro):
        stage = lax.axis_index(pp_axis)
        my_state = jax.tree_util.tree_map(lambda p: p[0], stacked_state)
        total = num_micro + num_stages - 1

        if embed_apply is not None:
            x_micro = embed_apply(aux_state.get("embed"), x_micro)

        # activation shape = embed output of one micro-batch; mark it
        # varying over pp (the replicated zeros become rank-dependent once
        # ppermute rotates real activations through the carry)
        act0 = lax.pcast(jnp.zeros_like(x_micro[0]), (pp_axis,), to="varying")

        def tick(buf, t):
            # stage 0 injects micro-batch t (clamped index; masked later)
            idx = jnp.clip(t, 0, num_micro - 1)
            x_t = lax.dynamic_index_in_dim(x_micro, idx, 0, keepdims=False)
            inp = jnp.where(stage == 0, x_t, buf)
            out = stage_apply(my_state, inp)
            n = lax.axis_size(pp_axis)
            sent = lax.ppermute(out, pp_axis, [(i, (i + 1) % n) for i in range(n)])
            return sent, out

        _, outs = lax.scan(tick, act0, jnp.arange(total))
        # last stage's valid outputs are ticks [S-1, S-1+M)
        y = lax.slice_in_dim(outs, num_stages - 1, num_stages - 1 + num_micro, axis=0)
        if head_apply is not None:
            y = head_apply(aux_state.get("head"), y)
        # only the last stage computed real outputs; replicate via masked
        # psum. The psum is DIFFERENTIATED by callers (hybrid's
        # value_and_grad runs straight through the pipe), and its
        # downstream cotangent is replicated over pp (every rank computes
        # the same loss from the replicated output) — so it is the
        # identity-VJP psum, correct under both check_vma settings
        # (oracle: test_hybrid_grads_match_serial). The is_last mask
        # then hands the unscaled cotangent to the last rank's path
        # only, which is also exactly what the f_then_b trainer's masked
        # local loss seeds, so both callers stay correct.
        is_last = (stage == num_stages - 1).astype(y.dtype)
        y = coll.psum_replicated(y * is_last, pp_axis)
        return y

    return fn


class PipelineTrainer:
    """Compiled pipeline training over the pp axis of a mesh.

    Mirrors the role of PipelineTrainer/SectionWorker: owns stage state,
    runs fwd+bwd+update as one jitted SPMD program per step.

    ``schedule``:
    - ``"f_then_b"`` — all forwards then all backwards (autodiff through
      the forward scan; activation memory O(num_micro) per rank). The
      SectionWorker F-then-B program (section_worker.cc:92-138).
    - ``"1f1b"`` — one-forward-one-backward with a bounded 2S-slot
      activation stash + per-stage recompute (section_worker.cc:139-189).
    - ``"interleave"`` — Megatron-style interleaved 1F1B with
      ``num_virtual`` chunks per rank (pipeline_parallel.py:30 dygraph
      interleave); model must supply ``pp × num_virtual`` stages.
      Arbitrary micro counts are handled by masking the padded tail of
      the schedule (see parallel/pipeline_1f1b.py).

    When the mesh has a ``dp_axis`` axis, each micro-batch SHARDS over
    it and the loss is the mean of the per-shard means — ``loss_fn``
    must therefore be a per-batch MEAN reduction (sum-style losses
    would silently scale by 1/dp). Single-axis meshes replicate.
    """

    def __init__(
        self,
        model: PipelineLayer,
        optimizer,
        loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
        mesh: Mesh,
        num_micro: int,
        pp_axis: str = "pp",
        seed: int = 0,
        schedule: str = "f_then_b",
        num_virtual: int = 1,
        dp_axis: str = "dp",
    ) -> None:
        enforce(pp_axis in mesh.shape, f"mesh lacks {pp_axis!r} axis")
        enforce(schedule in ("f_then_b", "1f1b", "interleave"),
                f"unknown schedule {schedule!r}")
        V = num_virtual if schedule == "interleave" else 1
        enforce_eq(mesh.shape[pp_axis] * V, model.num_stages,
                   "stages must equal pp size × num_virtual")
        self.model = model
        self.mesh = mesh
        self.num_micro = num_micro
        self.optimizer = optimizer
        self.schedule = schedule

        stacked = model.stage_stacked_state()
        aux = model.aux_state()
        self._params = {"stages": stacked, "aux": aux}
        self.opt_state = optimizer.init(self._params)

        S = model.num_stages // V

        def stage_apply(state, x):
            out, _ = nn.functional_call(model.stages[0], state, x, training=True)
            return out

        def embed_apply(state, x):
            if state is None:
                return x
            out, _ = nn.functional_call(model._sub_layers["embed"], state, x, training=True)
            return out

        def head_apply(state, y):
            if state is None:
                return y
            out, _ = nn.functional_call(model._sub_layers["head"], state, y, training=True)
            return out

        # batch parallelism: when the mesh has the dp axis, micro-batches
        # shard over it (dim 1 of [M, micro, ...]) instead of every dp
        # rank redundantly computing the full batch
        dp_axis = dp_axis if dp_axis in mesh.shape else None
        dp_n = mesh.shape.get(dp_axis, 1) if dp_axis else 1
        self._dp_n = dp_n
        data_spec = P(None, dp_axis) if dp_axis else P()

        def global_mean(local):
            # local = mean over this rank's batch shard (equal sizes)
            return (lax.psum(local / dp_n, dp_axis) if dp_axis else local)

        if schedule == "f_then_b":
            pipe = pipeline_spmd_fn(
                stage_apply, S, num_micro, pp_axis,
                embed_apply if aux.get("embed") else None,
                head_apply if aux.get("head") else None,
            )

            def spmd_local_loss(params, x_micro, y_micro, rng):
                # distinct stochastic streams per pipeline stage
                key = jax.random.fold_in(rng, lax.axis_index(pp_axis))
                with nn.rng_guard(key):
                    preds = pipe(params["stages"], params["aux"], x_micro)
                # mean over micro-batches of per-micro loss, COUNTED ON
                # THE LAST pp RANK ONLY: the grads below are reduced
                # explicitly over pp, so letting all S ranks seed an
                # identical loss would scale every gradient by S
                losses = jax.vmap(loss_fn)(preds, y_micro)
                r = lax.axis_index(pp_axis)
                return jnp.where(r == lax.axis_size(pp_axis) - 1,
                                 jnp.mean(losses), 0.0)

            def spmd_vg(params, x_micro, y_micro, rng):
                loss, grads = jax.value_and_grad(spmd_local_loss)(
                    params, x_micro, y_micro, rng)
                # explicit cross-rank reductions, NOT autodiff through a
                # psum'd loss. aux grads live on single pp ranks —
                # embed's chain ends on rank 0, head's on rank S-1 — so
                # they replicate by pp-psum exactly as the 1f1b branch
                # does below; the loss value does the same.
                loss = global_mean(lax.psum(loss, pp_axis))
                red_axes = lambda extra: extra + (
                    (dp_axis,) if dp_axis else ())
                g_stage = grads["stages"]
                if dp_axis:
                    g_stage = jax.tree_util.tree_map(
                        lambda g: lax.psum(g, dp_axis) / dp_n, g_stage)
                g_aux = jax.tree_util.tree_map(
                    lambda g: lax.psum(g, red_axes((pp_axis,))) / dp_n,
                    grads["aux"])
                return loss, {"stages": g_stage, "aux": g_aux}

            stage_specs = jax.tree_util.tree_map(lambda _: P(pp_axis), stacked)
            aux_specs = jax.tree_util.tree_map(lambda _: P(), aux)
            param_specs = {"stages": stage_specs, "aux": aux_specs}

            # check_vma=False like the 1f1b branch: every cross-rank
            # reduction above is explicit, and a vma-typed autodiff
            # would add its own on top of them
            grad_fn = shard_map(
                spmd_vg,
                mesh=mesh,
                in_specs=(param_specs, data_spec, data_spec, P()),
                out_specs=(P(), param_specs),
                check_vma=False,
            )

            def step(params, opt_state, x_micro, y_micro, rng):
                loss, grads = grad_fn(params, x_micro, y_micro, rng)
                new_params, new_opt = optimizer.update(grads, opt_state, params)
                return new_params, new_opt, loss

        else:
            from .pipeline_1f1b import pipeline_1f1b_fn

            pipe = pipeline_1f1b_fn(
                stage_apply, S, V, num_micro, loss_fn, pp_axis,
                embed_apply if aux.get("embed") else None,
                head_apply if aux.get("head") else None,
            )
            M = num_micro

            def spmd_grad(params_vs, x_micro, y_micro, rng):
                key = jax.random.fold_in(rng, lax.axis_index(pp_axis))
                # local chunk view: [V, 1, ...] → [V, ...]
                chunk_state = jax.tree_util.tree_map(
                    lambda p: p[:, 0], params_vs["stages"])
                with nn.rng_guard(key):
                    loss, g_stage, g_aux = pipe(
                        chunk_state, params_vs["aux"], x_micro, y_micro)
                # loss/aux grads live on single pp ranks — replicate by
                # psum; explicit grads also need the dp batch reduction
                # the f_then_b path gets implicitly from autodiff
                loss = global_mean(lax.psum(loss, pp_axis))
                dp_axes = (pp_axis,) + ((dp_axis,) if dp_axis else ())
                g_aux = jax.tree_util.tree_map(
                    lambda g: lax.psum(g, dp_axes) / (M * dp_n), g_aux)
                if dp_axis:
                    g_stage = jax.tree_util.tree_map(
                        lambda g: lax.psum(g, dp_axis), g_stage)
                g_stage = jax.tree_util.tree_map(
                    lambda g: g[:, None] / (M * dp_n), g_stage)
                return loss, {"stages": g_stage, "aux": g_aux}

            stage_specs_vs = jax.tree_util.tree_map(
                lambda _: P(None, pp_axis), stacked)
            aux_specs = jax.tree_util.tree_map(lambda _: P(), aux)
            grad_fn = shard_map(
                spmd_grad,
                mesh=mesh,
                in_specs=({"stages": stage_specs_vs, "aux": aux_specs},
                          data_spec, data_spec, P()),
                out_specs=(P(), {"stages": stage_specs_vs, "aux": aux_specs}),
                check_vma=False,
            )

            def step(params, opt_state, x_micro, y_micro, rng):
                stages_vs = jax.tree_util.tree_map(
                    lambda p: p.reshape(V, S, *p.shape[1:]),
                    params["stages"])
                loss, grads_vs = grad_fn(
                    {"stages": stages_vs, "aux": params["aux"]},
                    x_micro, y_micro, rng)
                g_stages = jax.tree_util.tree_map(
                    lambda g: g.reshape(V * S, *g.shape[2:]),
                    grads_vs["stages"])
                grads = {"stages": g_stages, "aux": grads_vs["aux"]}
                new_params, new_opt = optimizer.update(grads, opt_state, params)
                return new_params, new_opt, loss

        self._step = jax.jit(step, donate_argnums=(0, 1))
        self._rng = jax.random.key(seed)
        self.global_step = 0

    def save(self, path: str) -> None:
        """Persist stage+aux params, optimizer state, rng, and step
        (shared trainer-snapshot schema)."""
        from ..io.checkpoint import save_train_state

        save_train_state(path, self._params, opt_state=self.opt_state,
                         rng=self._rng, step=self.global_step)

    def load(self, path: str) -> None:
        """Restore a snapshot saved by :meth:`save`; values graft into
        the live pytrees (container types preserved, mesh shardings
        reused where a compiled step set them)."""
        from ..io.checkpoint import graft_into, load_train_state

        snap = load_train_state(path)
        self._params = graft_into(self._params, snap["state"])
        self.opt_state = graft_into(self.opt_state, snap["opt"])
        if snap["rng"] is not None:
            self._rng = snap["rng"]
        self.global_step = snap["step"]

    def train_step(self, x: jax.Array, y: jax.Array) -> jax.Array:
        """x, y: [batch, ...] split into num_micro micro-batches on dim 0
        (each micro-batch then shards over the mesh's dp axis)."""
        B = x.shape[0]
        enforce_eq(B % self.num_micro, 0, f"batch size {B} must be divisible by num_micro={self.num_micro}")
        enforce_eq((B // self.num_micro) % self._dp_n, 0,
                   f"micro-batch {B // self.num_micro} must divide over "
                   f"dp={self._dp_n}")
        xm = x.reshape(self.num_micro, B // self.num_micro, *x.shape[1:])
        ym = y.reshape(self.num_micro, B // self.num_micro, *y.shape[1:])
        self._rng, sub = jax.random.split(self._rng)
        self._params, self.opt_state, loss = self._step(
            self._params, self.opt_state, xm, ym, sub
        )
        self.global_step += 1
        return loss

    def sync_model(self) -> PipelineLayer:
        host = jax.device_get(self._params)
        for i, stage in enumerate(self.model.stages):
            nn.set_state(
                stage, jax.tree_util.tree_map(lambda p: p[i], host["stages"])
            )
        for name in ("embed", "head"):
            if name in host["aux"]:
                nn.set_state(self.model._sub_layers[name], host["aux"][name])
        return self.model

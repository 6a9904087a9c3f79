"""Auto-parallel (reference ``python/paddle/distributed/auto_parallel/``).

The reference's semi-automatic pipeline — ``ProcessMesh`` + per-tensor
``dims_mapping`` dist-attrs (interface.py shard_tensor), a ``Completer``
that propagates annotations over the graph (completion.py), a
``Partitioner`` that rewrites the serial program into per-rank programs
(partitioner.py), ``Resharder`` inserting send/recv for mismatched
shardings (reshard.py), all driven by ``Engine`` (engine.py:50) —
maps almost one-to-one onto GSPMD:

- ``ProcessMesh``            → ``jax.sharding.Mesh`` (named axes)
- ``shard_tensor(dims_mapping)`` → ``NamedSharding``/``device_put`` (data)
  or ``lax.with_sharding_constraint`` (in-graph annotation)
- Completer + Partitioner + Resharder → XLA's GSPMD propagation pass:
  jit with a few annotations *is* the completion algorithm, and resharding
  collectives are inserted by the compiler.

``Engine`` keeps the reference's prepare/fit/evaluate/predict surface.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.enforce import InvalidArgumentError, enforce
from .. import nn
from ..optimizer import Optimizer

__all__ = ["ProcessMesh", "shard_tensor", "shard_op", "annotate",
           "complete_shardings", "reshard", "plan_strategy", "Engine",
           "ClusterSpec", "estimate_plan_cost", "choose_strategy",
           "hybrid_trainer_from_plan"]


class ProcessMesh:
    """Reference ``ProcessMesh`` (process_mesh.py): an N-D array of
    process/device ids with named dimensions. Thin wrapper producing a
    ``jax.sharding.Mesh`` over the local device set."""

    def __init__(self, mesh: Optional[Sequence] = None,
                 dim_names: Optional[Sequence[str]] = None,
                 shape: Optional[Sequence[int]] = None) -> None:
        if shape is None:
            arr = np.asarray(mesh if mesh is not None else [])
            shape = arr.shape if arr.size else (len(jax.devices()),)
        self.shape = tuple(int(s) for s in shape)
        self.dim_names = list(dim_names or [f"d{i}" for i in range(len(self.shape))])
        enforce(len(self.dim_names) == len(self.shape),
                "dim_names must match mesh rank")
        n = int(np.prod(self.shape))
        devs = jax.devices()
        enforce(n <= len(devs), f"mesh wants {n} devices, have {len(devs)}")
        self.jax_mesh = Mesh(np.asarray(devs[:n]).reshape(self.shape),
                             tuple(self.dim_names))

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def __repr__(self) -> str:
        return f"ProcessMesh(shape={self.shape}, dim_names={self.dim_names})"


def _spec_from_dims_mapping(mesh: ProcessMesh, dims_mapping: Sequence[Optional[int]]
                            ) -> PartitionSpec:
    """dims_mapping[i] = index of the mesh dim tensor-dim i is split
    over, or None/-1 for replicated (the reference's convention)."""
    entries = []
    for m in dims_mapping:
        if m is None or m == -1:
            entries.append(None)
        else:
            enforce(0 <= m < mesh.ndim, f"dims_mapping entry {m} out of range")
            entries.append(mesh.dim_names[m])
    return PartitionSpec(*entries)


def shard_tensor(x, process_mesh: ProcessMesh,
                 dims_mapping: Sequence[Optional[int]]):
    """Reference ``auto_parallel.shard_tensor`` (interface.py): attach a
    sharding to a concrete array (device_put) or, when traced inside
    jit, constrain the intermediate's sharding so GSPMD completes the
    rest of the graph around it."""
    spec = _spec_from_dims_mapping(process_mesh, dims_mapping)
    sharding = NamedSharding(process_mesh.jax_mesh, spec)
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(jnp.asarray(x), sharding)


def annotate(x, process_mesh: ProcessMesh, dims_mapping: Sequence[Optional[int]]):
    """In-graph-only spelling of shard_tensor."""
    spec = _spec_from_dims_mapping(process_mesh, dims_mapping)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(process_mesh.jax_mesh, spec))


def shard_op(fn: Callable, process_mesh: ProcessMesh,
             out_dims_mappings: Optional[Sequence[Sequence[Optional[int]]]] = None
             ) -> Callable:
    """Reference ``shard_op``: annotate an op's outputs. GSPMD then
    propagates through the op body."""

    def wrapped(*args, **kwargs):
        out = fn(*args, **kwargs)
        if out_dims_mappings is None:
            return out
        outs = out if isinstance(out, tuple) else (out,)
        enforce(len(outs) == len(out_dims_mappings),
                "one dims_mapping per output")
        annotated = tuple(
            annotate(o, process_mesh, dm) for o, dm in zip(outs, out_dims_mappings))
        return annotated if isinstance(out, tuple) else annotated[0]

    return wrapped


def _named_leaf_layers(layer, prefix=""):
    """Ordered (name, layer) leaves that own parameters — registration
    order, which matches forward order for the sequential compositions
    the completion rules cover."""
    out = []
    if layer._parameters:
        out.append((prefix, layer))
    for sub_name, sub in layer._sub_layers.items():
        sub_prefix = sub_name if not prefix else f"{prefix}.{sub_name}"
        out.extend(_named_leaf_layers(sub, sub_prefix))
    return out


def _axis_of(spec_entry):
    return spec_entry if isinstance(spec_entry, str) else None


def _canon(*entries) -> PartitionSpec:
    """Canonical spec: trailing replicated dims dropped (so results
    compare equal to hand-written PartitionSpecs)."""
    es = list(entries)
    while es and es[-1] is None:
        es.pop()
    return PartitionSpec(*es)


def complete_shardings(
    model,
    process_mesh: ProcessMesh,
    annotations: Dict[str, Sequence[Optional[int]]],
    example_inputs: Optional[Sequence[Any]] = None,
) -> Dict[str, PartitionSpec]:
    """The Completer (reference ``auto_parallel/completion.py``): from one
    or two user dist-attr hints, derive a PartitionSpec for EVERY
    parameter by greedy propagation over the layer graph.

    ``annotations``: {param_name: dims_mapping} in the reference's
    convention (entry = mesh-dim index or -1/None for replicated).

    With ``example_inputs`` (arrays or ShapeDtypeStructs), completion
    runs on the TRACED dataflow graph (completion.py — jaxpr-level:
    handles branching QKV, residual blocks, fused weights, repeated
    -block hint expansion; the reference Completer's arbitrary-graph
    coverage). Without inputs, the legacy sequential-chain walk below
    applies — correct for Linear/Embedding/Conv chains only.

    Sequential fallback: two passes over the ordered parameter-owning
    leaves:

    - **backward** (right-to-left): a user hint that row-shards a
      Linear's input dim over axis *a* demands its producer emit
      *a*-sharded features — an unannotated upstream Linear is assigned
      the column-parallel layout (out dim on *a*), the Megatron pairing
      completion.py derives from op dist-attr rules.
    - **forward** (left-to-right): track the mesh axis the activation's
      feature dim is currently sharded over; a column-parallel Linear
      shards its bias and the downstream activation; an unannotated
      Linear consuming *a*-sharded features becomes row-parallel (in dim
      on *a*, replicated output — XLA inserts the psum); LayerNorm/other
      1-D params replicate.

    The result feeds ``Engine`` parameter placement; XLA's GSPMD then
    completes every *intermediate* tensor (the rest of completion.py's
    job) during jit."""
    if example_inputs is not None:
        from .completion import complete_shardings_traced

        return complete_shardings_traced(model, process_mesh, annotations,
                                         example_inputs)
    mesh = process_mesh
    leaves = _named_leaf_layers(model)
    user: Dict[str, PartitionSpec] = {
        name: _spec_from_dims_mapping(mesh, dm)
        for name, dm in annotations.items()
    }
    from ..nn.layers import Conv2D, Embedding, LayerNorm, Linear

    assigned: Dict[str, PartitionSpec] = {}  # per-layer weight specs

    def w_name(name):
        return f"{name}.weight" if name else "weight"

    # -- backward pass: produce col-parallel partners for row hints ------
    need: Optional[str] = None  # axis the producer's output must carry
    for name, layer in reversed(leaves):
        wn = w_name(name)
        if isinstance(layer, Linear):
            if wn in user:
                spec = tuple(user[wn])
                need = _axis_of(spec[0]) if spec else None
            elif need is not None:
                assigned[wn] = PartitionSpec(None, need)  # column-parallel
                need = None
            else:
                need = None
        elif isinstance(layer, LayerNorm):
            pass  # feature-preserving: the demand flows through
        else:
            need = None

    # -- forward pass: propagate the activation's feature-dim axis ------
    specs: Dict[str, PartitionSpec] = {}
    act: Optional[str] = None
    for name, layer in leaves:
        wn = w_name(name)
        pnames = list(layer._parameters)

        def put(pname, spec):
            full = f"{name}.{pname}" if name else pname
            specs[full] = user.get(full, spec)

        if isinstance(layer, Linear):
            if wn in user:
                w = user[wn]
            elif wn in assigned:
                w = assigned[wn]
            elif act is not None:
                w = PartitionSpec(act, None)  # row-parallel completion
            else:
                w = PartitionSpec()
            w = tuple(w) + (None,) * (2 - len(w))
            specs[wn] = _canon(*w)
            out_ax = _axis_of(w[1])
            if "bias" in pnames:
                put("bias", _canon(out_ax))
            act = out_ax  # row-parallel output is psum'd → replicated
        elif isinstance(layer, Embedding):
            w = tuple(user.get(wn, PartitionSpec()))
            specs[wn] = _canon(*w)
            hidden_ax = _axis_of(w[1]) if len(w) > 1 else None
            act = hidden_ax  # vocab-parallel output psums → replicated
        elif isinstance(layer, Conv2D):
            if wn in user:
                w = tuple(user[wn])
            elif act is not None:
                w = (None, act, None, None)  # in-channels (row analogue)
            else:
                w = ()
            specs[wn] = _canon(*w)
            out_ax = _axis_of(w[0]) if len(w) > 0 else None
            if "bias" in pnames:
                put("bias", _canon(out_ax))
            act = out_ax
        else:
            # LayerNorm/BatchNorm/etc: 1-D params replicate (the norm
            # reads full features; GSPMD gathers if needed)
            for pname in pnames:
                put(pname, PartitionSpec())
    return specs


def _pipeline_stages(model, graph=None) -> int:
    """Largest homogeneous repeated-block count in the model — the max
    usable pipeline depth (reference planner partitions programs at
    block boundaries; a model with no repeated blocks can't pipeline).
    Counted from LayerList children whose entries share one class.

    With a traced param graph (completion.trace_param_graph), a
    candidate list must also be SEQUENTIAL in the dataflow — block i
    consuming block i-1's outputs. A LayerList of parallel experts
    (MoE) or multi-branch heads is structurally homogeneous but has no
    stage boundaries; the trace tells them apart."""
    from ..nn.layer import LayerList

    def sequential(prefix: str, n: int) -> bool:
        if graph is None:
            return True  # structural fallback: assume sequential
        for i in range(1, n):
            prev = {u.name for u in graph.uses
                    if u.name.startswith(f"{prefix}.{i - 1}.")}
            cur = [u for u in graph.uses
                   if u.name.startswith(f"{prefix}.{i}.")]
            if not cur or not any(u.preds & prev for u in cur):
                return False
        return True

    best = 1
    stack = [(model, "")]
    while stack:
        layer, prefix = stack.pop()
        for name, sub in layer._sub_layers.items():
            q = f"{prefix}.{name}" if prefix else name
            if (isinstance(sub, LayerList) and len(sub) > 1
                    and len({type(b) for b in sub}) == 1
                    and sequential(q, len(sub))):
                best = max(best, len(sub))
            stack.append((sub, q))
    return best


def _mp_annotations(model, mp: int,
                    example_inputs: Optional[Sequence[Any]] = None,
                    ) -> Dict[str, Sequence[Optional[int]]]:
    """The planner's hint rule, shared by :func:`plan_strategy` and
    :func:`choose_strategy`: large Linears in alternating Megatron
    col/row pairs, Embeddings vocab- or hidden-parallel; completion
    fills the rest. Only dims divisible by mp qualify.

    With ``example_inputs`` the pairing runs on the TRACED dataflow
    (completion.mp_annotations_traced — exact for branching graphs,
    fused QKV, residuals); otherwise on registration order (sequential
    chains only)."""
    if example_inputs is not None:
        from .completion import mp_annotations_traced

        return mp_annotations_traced(model, mp, 1, example_inputs)
    from ..nn.layers import Embedding, Linear

    annotations: Dict[str, Sequence[Optional[int]]] = {}
    sizes = [int(np.prod(l._parameters["weight"].shape))
             for _, l in _named_leaf_layers(model)
             if isinstance(l, (Linear, Embedding))
             and "weight" in l._parameters]
    threshold = max(sizes, default=0) // 4
    col_next = True
    for name, layer in _named_leaf_layers(model):
        w = layer._parameters.get("weight")
        wn = f"{name}.weight" if name else "weight"
        if w is None or int(np.prod(w.shape)) < threshold:
            continue
        if isinstance(layer, Linear):
            if col_next and w.shape[1] % mp == 0:
                annotations[wn] = [-1, 1]   # column-parallel
                col_next = False
            elif not col_next and w.shape[0] % mp == 0:
                annotations[wn] = [1, -1]   # row-parallel partner
                col_next = True
        elif isinstance(layer, Embedding):
            if w.shape[0] % mp == 0:
                annotations[wn] = [1, -1]   # vocab-parallel
            elif w.shape[1] % mp == 0:
                annotations[wn] = [-1, 1]   # hidden-parallel
    return annotations


def plan_strategy(model, n_devices: Optional[int] = None,
                  per_device_bytes: float = 16e9,
                  state_multiplier: float = 4.0,
                  ) -> Tuple[ProcessMesh, Dict[str, Sequence[Optional[int]]]]:
    """The Planner (reference ``auto_parallel/planner_v2.py`` role):
    pick a (dp, mp) mesh factorization and the dist-attr hints that make
    the model fit, automatically.

    Memory model: training state ≈ ``state_multiplier`` × param bytes
    (f32 params + grads + Adam m/v). If that fits one device, pure data
    parallel wins (no comms beyond grad allreduce). Otherwise choose the
    smallest power-of-two ``mp`` that brings the per-device share under
    budget, and emit one column-parallel hint per large Megatron pair —
    :func:`complete_shardings` then derives the row partners, biases and
    norms. Returns ``(ProcessMesh(dp, mp), annotations)`` ready for
    :class:`Engine`.

    This is deliberately a greedy heuristic, not the reference's full
    cost-model search — it covers the planner's decision (which axis,
    which tensors) with an auditable rule."""
    devs = n_devices if n_devices is not None else len(jax.devices())
    params = dict(model.named_parameters())
    total = sum(int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
                for p in params.values())
    need = total * state_multiplier

    # mp walks power-of-two DIVISORS of the device count only — a
    # non-power-of-two slice gets the largest usable factor, never a
    # "cannot factor" crash
    mp = 1
    while need / mp > per_device_bytes:
        nxt = mp * 2
        if nxt > devs or devs % nxt != 0:
            break
        mp = nxt

    annotations: Dict[str, Sequence[Optional[int]]] = {}
    if mp > 1:
        annotations = _mp_annotations(model, mp)
        if not annotations:
            # nothing shardable at this mp (odd dims, embedding-free
            # budget blowup): an mp the plan cannot use would halve dp
            # for zero memory relief — fall back to pure dp, honestly
            mp = 1
    dp = devs // mp
    mesh = ProcessMesh(shape=(dp, mp), dim_names=("dp", "mp"))
    return mesh, annotations


@dataclasses.dataclass
class ClusterSpec:
    """The reference ``auto_parallel/cluster.py`` role: what the cost
    model needs to know about the machine — per-axis interconnect
    bandwidth. Convention: when ``hosts > 1`` the OUTERMOST mesh axis
    is the one laid across hosts (jax device order enumerates
    host-major), so that axis's collectives ride DCN; every inner axis
    rides ICI."""

    ici_gbytes_per_s: float = 90.0   # v5e all-reduce effective BW/chip
    dcn_gbytes_per_s: float = 6.0    # typical inter-host effective BW
    hosts: int = 1
    device_tflops: float = 197.0     # v5e bf16 peak — feeds the pp
    # bubble term only (plan-invariant compute divides out elsewhere)

    def axis_bw(self, axis_index: int, axis_size: int) -> float:
        if axis_size <= 1:
            return float("inf")
        if self.hosts > 1 and axis_index == 0:
            return self.dcn_gbytes_per_s
        return self.ici_gbytes_per_s


def estimate_plan_cost(model, mesh: ProcessMesh,
                       annotations: Dict[str, Sequence[Optional[int]]],
                       batch_tokens: int,
                       cluster: Optional[ClusterSpec] = None,
                       state_multiplier: float = 4.0,
                       microbatches: int = 8,
                       sh: int = 0,
                       recompute: bool = False) -> Dict[str, float]:
    """Analytic per-step cost of a (mesh, annotations) plan — the
    reference cost model's estimate (``auto_parallel/cost_model.py``,
    ``cost/comm_op_cost.py``) in closed form for the dominant terms of
    a dp × mp × pp plan:

    - dp gradient all-reduce: ring volume 2·(dp-1)/dp · param_bytes
      over the dp axis's link (mp-sharded tensors all-reduce only their
      1/mp shard; pp shards the params across stages → 1/pp);
    - mp activation all-reduce: each column→row Megatron pair psums a
      [batch_tokens, out_dim] activation in fwd and its gradient in bwd
      (2 × ring volume), where out_dim is the row-parallel layer's
      output width;
    - mp UNPAIRED column-parallel output all-gather: a col-annotated
      weight with no row partner leaves its activation mp-sharded; the
      next (replicated-weight) consumer forces an all-gather of the
      full [batch_tokens, out] — charged per unpaired col (pairing
      follows annotation-dict order, which both hint rules emit in
      dataflow order);
    - pp bubble: 1F1B idle fraction (pp-1)/microbatches of the
      per-device compute time (compute itself is plan-invariant —
      flops/device = flops/devices for every factorization — so only
      the bubble enters ``total_s``);
    - pp p2p: boundary activation sends, 2 × (pp-1) stage hops of
      [batch_tokens/dp, hidden] each way;
    - ``sh`` (ZeRO stage over the dp axis — the reference's sharding
      stages, distributed_strategy.proto:32-49, executed by
      ``parallel/spmd.py``/``parallel/sharding.py``): memory relief
      stage 1 = optimizer state /dp, stage 2 = + grads /dp, stage 3 =
      + params /dp. Comms: stages 1-2 keep the allreduce ring volume
      (ring allreduce ≡ reduce-scatter + all-gather, which is exactly
      ZeRO-2's grad-RS + param-AG); stage 3 re-gathers params in fwd
      AND bwd — charged as one extra ring volume;
    - ``recompute``: activation memory drops to block boundaries
      (/ n_layers) at the price of one extra forward — + compute/3
      (fwd is 2PB of the 6PB fwd+bwd total), charged to ``total_s``
      because it is toggle-variant even though plan-invariant.

    Memory decomposes as params + grads + optimizer state
    (``state_multiplier`` − 2 of it) + activations (batch_tokens/dp/pp ×
    hidden × n_layers floats), each term with its sh/recompute relief.

    Returns an auditable dict: bytes and seconds per term plus
    ``per_device_state_bytes`` (the memory-fit input) and ``total_s``.
    Absolute numbers are estimates; their ORDER over candidate plans is
    what ``choose_strategy`` consumes — the reference's cost model has
    the same contract.
    """
    cluster = cluster or ClusterSpec()
    dims = dict(zip(mesh.dim_names, mesh.shape))
    dp = int(dims.get("dp", 1))
    mp = int(dims.get("mp", 1))
    pp = int(dims.get("pp", 1))
    names = list(mesh.dim_names)
    dp_ax = names.index("dp") if "dp" in names else 0
    mp_ax = names.index("mp") if "mp" in names else 1

    params = dict(model.named_parameters())
    sharded_bytes = 0.0
    unsharded_bytes = 0.0
    total_count = 0
    for name, p in params.items():
        cnt = int(np.prod(p.shape))
        total_count += cnt
        b = float(cnt * np.dtype(p.dtype).itemsize)
        sharded = name in annotations and any(
            d is not None and d >= 0
            for d in annotations[name])
        if sharded:
            sharded_bytes += b
        else:
            unsharded_bytes += b
    # mp shards only the ANNOTATED tensors (completion shards a few
    # more — partners, biases — so this memory estimate is conservative,
    # never optimistic); grads all-reduce at the same granularity.
    # pp splits stages: uniform 1/pp share approximation.
    dp_grad_bytes = (sharded_bytes / mp + unsharded_bytes) / pp
    ring = lambda n: 2.0 * (n - 1) / n if n > 1 else 0.0
    dp_s = (ring(dp) * dp_grad_bytes
            / (cluster.axis_bw(dp_ax, dp) * 1e9))
    sh = int(sh) if dp > 1 else 0  # ZeRO over a 1-wide dp axis is a no-op
    sh_extra_s = 0.0
    if sh >= 3:
        # stage-3 re-gathers the param shards before fwd and bwd
        sh_extra_s = dp_s
    dp_s += sh_extra_s

    # mp activation collectives: walk annotations in order keeping the
    # open column-parallel stack — row partners psum, unpaired cols at
    # the end all-gather their sharded output
    mp_act_bytes = 0.0
    mp_gather_bytes = 0.0
    if mp > 1:
        open_col_widths: List[float] = []
        for name, spec in annotations.items():
            p = params.get(name)
            if p is None or len(p.shape) not in (2, 4):
                continue
            # only MP-axis shards are mp collectives — a dp-axis shard
            # (ZeRO-style placement) must not charge phantom psums
            sdims = [d for d, m in enumerate(spec) if m == mp_ax]
            if len(sdims) != 1:
                continue
            sdim = sdims[0]
            # role + activation width by layout: 2-D [in, out] (row =
            # dim 0, width = out); 4-D OIHW conv (col = out-chan dim 0,
            # row = in-chan dim 1, width = out channels; a spatial
            # shard is not a Megatron pattern and charges nothing)
            if len(p.shape) == 2:
                if sdim == 0:
                    is_row, width = True, float(p.shape[1])
                else:
                    is_row, width = False, float(p.shape[1])
            else:
                if sdim == 0:
                    is_row, width = False, float(p.shape[0])
                elif sdim == 1:
                    is_row, width = True, float(p.shape[0])
                else:
                    continue
            if is_row:
                # row-parallel: output [batch_tokens, out] is psummed.
                # A row partner closes ALL open cols — separate Q/K/V
                # emit col,col,col,row and the one row output absorbs
                # all three (mp_annotations_traced's `closing` loop
                # discards every pred); pop-one would charge the other
                # two phantom gathers
                mp_act_bytes += 2.0 * batch_tokens * width * 4.0
                open_col_widths.clear()
            else:
                open_col_widths.append(width)
        for width in open_col_widths:  # ADVICE r3: unpaired col gathers
            mp_gather_bytes += 2.0 * batch_tokens * width * 4.0
        # dp/pp shard the batch/stages: each group sees its local slice
        mp_act_bytes /= max(dp, 1) * max(pp, 1)
        mp_gather_bytes /= max(dp, 1) * max(pp, 1)
    mp_bw = cluster.axis_bw(mp_ax, mp) * 1e9
    mp_s = (ring(mp) * mp_act_bytes + ring(mp) * mp_gather_bytes) / mp_bw

    # per-device compute (plan-invariant across factorizations, but the
    # recompute toggle re-spends a forward of it)
    flops = 6.0 * total_count * batch_tokens  # fwd 2PB + bwd 4PB
    compute_s = flops / (dp * mp * pp) / (cluster.device_tflops * 1e12)
    two_d = [min(int(p.shape[0]), int(p.shape[1]))
             for p in params.values() if len(p.shape) == 2]
    hidden = float(max(two_d, default=0))
    n_layers = max(len(two_d), 1)

    # pp: bubble fraction of per-device compute + boundary p2p
    bubble_s = 0.0
    pp_p2p_s = 0.0
    if pp > 1:
        bubble_s = compute_s * (pp - 1) / max(microbatches, 1)
        pp_p2p_s = (2.0 * (pp - 1) * (batch_tokens / dp) * hidden * 4.0
                    / (cluster.ici_gbytes_per_s * 1e9))

    recompute_s = compute_s / 3.0 if recompute else 0.0

    # memory: params + grads + optimizer state + activations, each with
    # its sh / recompute relief
    param_pd = (sharded_bytes / mp + unsharded_bytes) / pp
    opt_mult = max(state_multiplier - 2.0, 0.0)
    shard = lambda stage_at_least: dp if sh >= stage_at_least else 1.0
    mem_params = param_pd / shard(3)
    mem_grads = param_pd / shard(2)
    mem_opt = param_pd * opt_mult / shard(1)
    act_full = (batch_tokens / max(dp, 1) / max(pp, 1)) * hidden \
        * n_layers * 4.0
    mem_act = act_full / (n_layers if recompute else 1)
    per_device_state = mem_params + mem_grads + mem_opt + mem_act
    return {
        "dp": dp, "mp": mp, "pp": pp, "sh": sh,
        "recompute": bool(recompute),
        "dp_allreduce_bytes": dp_grad_bytes * ring(dp),
        "dp_allreduce_s": dp_s,
        "sh_extra_s": sh_extra_s,
        "mp_activation_bytes": mp_act_bytes * ring(mp),
        "mp_gather_bytes": mp_gather_bytes * ring(mp),
        "mp_activation_s": mp_s,
        "pp_bubble_s": bubble_s,
        "pp_p2p_s": pp_p2p_s,
        "recompute_s": recompute_s,
        "param_bytes": mem_params,
        "grad_bytes": mem_grads,
        "opt_state_bytes": mem_opt,
        "activation_bytes": mem_act,
        "per_device_state_bytes": per_device_state,
        "total_s": dp_s + mp_s + bubble_s + pp_p2p_s + recompute_s,
    }


def choose_strategy(model, batch_tokens: int,
                    n_devices: Optional[int] = None,
                    per_device_bytes: float = 16e9,
                    cluster: Optional[ClusterSpec] = None,
                    state_multiplier: float = 4.0,
                    microbatches: int = 8,
                    example_inputs: Optional[Sequence[Any]] = None,
                    allow_pp: bool = True,
                    allow_sh=True,  # bool, or int = max ZeRO stage
                    ) -> Tuple[ProcessMesh,
                               Dict[str, Sequence[Optional[int]]],
                               List[Dict[str, float]]]:
    """The Planner's cost-model search (reference planner_v2 + cost
    model, ``auto_parallel/planner_v2.py``/``cost_model.py``): enumerate
    every power-of-two (dp, mp, pp) factorization of the device count
    (pp capped by the model's repeated-block depth,
    :func:`_pipeline_stages`) × ZeRO stage sh ∈ {0..3} over the dp axis
    (the reference's sharding stages, distributed_strategy.proto:32-49)
    × the recompute toggle, derive each one's dist-attr hints (the
    same rule :func:`plan_strategy` applies; dataflow-exact when
    ``example_inputs`` is given), drop plans that don't fit
    ``per_device_bytes`` or can't actually shard anything at their mp,
    and return the feasible plan with the lowest estimated step
    overhead (comm + pipeline bubble + recomputed fwd — per-device
    compute is otherwise plan-invariant and excluded). Also returns the
    full scored candidate list (auditable — the reference logs the
    same); the selected row carries ``chosen: True`` and its ``sh`` /
    ``recompute`` fields say how to execute it (sh via
    ``parallel.sharding``/``parallel.spmd``; the mesh stays (dp,mp,pp)).
    A model that fits under ZeRO-2 but not plain dp×mp now gets an sh
    plan — memory relief WITHOUT the pipeline bubble — instead of the
    pp plan it doesn't need. Executor routing by stage: stage 1 →
    ``hybrid_trainer_from_plan(..., sh=dp)`` (slot sharding at full dp
    width) or plain Engine+optimizer-state sharding; stages 2-3 →
    ``parallel/spmd.py``/``parallel/sharding.py`` (GSPMD grad/param
    sharding). The hybrid trainer's ``sh`` argument is a group WIDTH,
    not this stage number — see its docstring.

    When nothing fits, falls back to the MEMORY-minimizing candidate
    (plan_strategy's escalation behavior), since memory, not comms, is
    then the binding constraint. A model that cannot shard at any mp
    (odd dims) but stacks repeated blocks gets its memory relief from
    pp — the (dp, mp, pp) answer the round-3 dp×mp-only search could
    not return.

    Execution split (mirrors the reference's planner/partitioner
    separation): dp/mp plans run through :class:`Engine` (GSPMD); a
    pp>1 plan must run through the pipeline trainer
    (``paddle_tpu.parallel.hybrid``/``parallel.pipeline``), which
    partitions the blocks into real stages — Engine rejects pp>1
    meshes loudly rather than replicate across the axis."""
    devs = n_devices if n_devices is not None else len(jax.devices())
    cluster = cluster or ClusterSpec()
    graph = None
    if example_inputs is not None:
        from .completion import trace_param_graph

        graph = trace_param_graph(model, example_inputs)  # trace ONCE
    max_pp = _pipeline_stages(model, graph) if allow_pp else 1
    candidates: List[Dict[str, float]] = []
    plans = {}
    ann_cache: Dict[int, Dict] = {}

    def ann_for(mp: int):
        if mp not in ann_cache:
            if graph is not None:
                from .completion import mp_annotations_traced

                ann_cache[mp] = mp_annotations_traced(
                    model, mp, 1, example_inputs, graph=graph)
            else:
                ann_cache[mp] = _mp_annotations(model, mp)
        return ann_cache[mp]

    mp = 1
    while mp <= devs:
        pp = 1
        while mp * pp <= devs and pp <= max_pp:
            if devs % (mp * pp) == 0:
                dp = devs // (mp * pp)
                mesh = ProcessMesh(shape=(dp, mp, pp),
                                   dim_names=("dp", "mp", "pp"))
                ann = ann_for(mp) if mp > 1 else {}
                if mp == 1 or ann:  # an mp that shards nothing: no plan
                    # sh (ZeRO stage over dp — the reference's sharding
                    # stages) and recompute widen the search: memory
                    # relief without the pp bubble. Enumeration order
                    # (sh ↑, recompute last) is the tie-break: at equal
                    # cost the LEAST mechanism wins.
                    # allow_sh: True = all stages, False/0 = none, an
                    # int caps the stage (Engine passes 1 — the stage
                    # its GSPMD executor delivers)
                    if dp > 1 and allow_sh:
                        max_stage = 3 if allow_sh is True else int(allow_sh)
                        sh_stages = tuple(range(0, max_stage + 1))
                    else:
                        sh_stages = (0,)
                    for sh in sh_stages:
                        for rc in (False, True):
                            cost = estimate_plan_cost(
                                model, mesh, ann, batch_tokens, cluster,
                                state_multiplier, microbatches,
                                sh=sh, recompute=rc)
                            cost["fits"] = bool(
                                cost["per_device_state_bytes"]
                                <= per_device_bytes)
                            candidates.append(cost)
                            plans[(dp, mp, pp, sh, rc)] = (mesh, ann)
            pp *= 2
        mp *= 2
    feasible = [c for c in candidates if c["fits"]]
    if feasible:
        best = min(feasible, key=lambda c: c["total_s"])
    else:
        # nothing fits: minimize MEMORY, not comms — the binding
        # constraint decides (plan_strategy's max-usable-mp behavior)
        best = min(candidates, key=lambda c: c["per_device_state_bytes"])
    best["chosen"] = True
    mesh, ann = plans[(int(best["dp"]), int(best["mp"]), int(best["pp"]),
                       int(best["sh"]), bool(best["recompute"]))]
    return mesh, ann, candidates


def hybrid_trainer_from_plan(cfg, process_mesh: ProcessMesh, optimizer,
                             num_micro: int = 2, seed: int = 0,
                             sh: int = 1):
    """Execute a :func:`choose_strategy` (dp, mp, pp) plan — the
    planner/partitioner split of the reference (planner_v2 emits the
    plan, the Partitioner + pipeline runtime execute it): dp/mp-only
    plans run through :class:`Engine` (GSPMD), while a pp-bearing plan
    runs HERE, through the pipeline trainer
    (``parallel.hybrid.HybridParallelTrainer``) on a 4-axis
    dp×pp×cp×mp mesh (cp=1) built from the plan's factorization.

    ``cfg`` is the model's :class:`~paddle_tpu.models.ernie.ErnieConfig`
    (the hybrid trainer's model family); ``process_mesh`` is the
    planner's mesh.

    ``sh`` here is a GROUP WIDTH (how many ranks of the dp axis form
    the inner ZeRO group; must divide dp) — NOT the planner's ZeRO
    *stage* number. Mapping a chosen plan: stage 1 (optimizer-state
    sharding) executes here with ``sh=dp`` — the hybrid trainer shards
    every optimizer slot over the sh group, which at full width IS the
    stage-1 memory the cost model charged. Stages 2-3 (grad/param
    sharding) are NOT what this trainer's sh axis implements — they
    execute through the GSPMD path (``parallel/spmd.py`` stage-2
    reduce-scatter / ``parallel/sharding.py``); passing a width here
    for a stage-2/3 plan under-delivers the planned memory relief.
    Returns the ready trainer — one ``train_step(ids, labels)`` per
    batch."""
    from jax.sharding import Mesh as JaxMesh

    from ..parallel.hybrid import HybridParallelTrainer

    dims = dict(zip(process_mesh.dim_names, process_mesh.shape))
    dp = int(dims.get("dp", 1))
    mp = int(dims.get("mp", 1))
    pp = int(dims.get("pp", 1))
    sh = max(int(sh), 1)
    n = dp * mp * pp
    if sh > 1:
        enforce(dp % sh == 0, f"sh={sh} must divide dp={dp}",
                InvalidArgumentError)
        devs = np.asarray(jax.devices()[:n]).reshape(dp // sh, pp, 1, mp, sh)
        mesh = JaxMesh(devs, ("dp", "pp", "cp", "mp", "sh"))
    else:
        devs = np.asarray(jax.devices()[:n]).reshape(dp, pp, 1, mp)
        mesh = JaxMesh(devs, ("dp", "pp", "cp", "mp"))
    return HybridParallelTrainer(cfg, mesh, optimizer,
                                 num_micro=num_micro, seed=seed)


def _insert_axis_spec(spec: PartitionSpec, shape: Sequence[int],
                      axis: str, size: int) -> PartitionSpec:
    """Add ``axis`` to a PartitionSpec on the first FREE dim divisible
    by ``size``; unchanged when no dim qualifies (the tensor stays at
    its parameter layout — same fallback as the hybrid trainer's sh
    insertion)."""
    t = tuple(spec) if spec is not None else ()
    t = t + (None,) * (len(shape) - len(t))
    for i, (ax, d) in enumerate(zip(t, shape)):
        if ax is None and d and d % size == 0:
            return PartitionSpec(*t[:i], axis, *t[i + 1:])
    return spec


def reshard(x, process_mesh: ProcessMesh,
            dims_mapping: Sequence[Optional[int]]):
    """The Resharder (reference ``auto_parallel/reshard.py``): move a
    tensor between shardings — including between DIFFERENT process
    meshes (pipeline program sections). Eagerly this is a device_put
    (XLA runtime moves/reassembles shards, the send/recv insertion
    reshard.py does by hand); on a traced value it becomes a sharding
    constraint and GSPMD inserts the collective."""
    spec = _spec_from_dims_mapping(process_mesh, dims_mapping)
    sharding = NamedSharding(process_mesh.jax_mesh, spec)
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)


class Engine:
    """Reference ``Engine`` (auto_parallel/engine.py:50): prepare →
    fit/evaluate/predict with automatic distribution. Here "planning +
    partitioning" is jit compilation over the ProcessMesh; the returned
    input shardings (``completion()``) show what GSPMD chose. Pass
    ``annotations`` ({param_name: dims_mapping}, one or two hints) to
    have :func:`complete_shardings` derive every parameter's layout."""

    def __init__(self, model: nn.Layer, loss_fn: Callable,
                 optimizer: Optimizer, process_mesh: Optional[ProcessMesh] = None,
                 batch_dim_mesh_axis: Optional[str] = None,
                 annotations: Optional[Dict[str, Sequence[Optional[int]]]] = None,
                 example_inputs: Optional[Sequence[Any]] = None,
                 plan: Optional[str] = None,
                 batch_tokens: int = 4096,
                 per_device_bytes: float = 16e9,
                 sharding_stage: int = 0,
                 ) -> None:
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # example_inputs (arrays or ShapeDtypeStructs): enables traced
        # graph-aware completion (branching models — see completion.py)
        self.example_inputs = example_inputs
        # stage-1 ZeRO (optimizer-state sharding over dp): slots persist
        # device-sharded between steps; the elementwise update computes
        # shard-locally and GSPMD all-gathers params for the forward —
        # sharding_optimizer.py stage-1 semantics executed by placement.
        # Stages 2-3 (grad/param sharding) need the explicit shard_map
        # formulation — parallel/spmd.py / parallel/sharding.py — and
        # are rejected here loudly.
        enforce(sharding_stage in (0, 1),
                f"Engine executes sharding stage 0 or 1; stage "
                f"{sharding_stage} (grad/param sharding) runs through "
                f"parallel.spmd / parallel.sharding", InvalidArgumentError)
        self.sharding_stage = int(sharding_stage)
        if plan == "auto":
            # the reference Engine's semi-auto mode: the cost-model
            # planner picks the (dp, mp) factorization AND the hints
            # (pp excluded — Engine executes GSPMD plans; pp plans run
            # via hybrid_trainer_from_plan)
            enforce(process_mesh is None and not annotations,
                    "plan='auto' derives mesh and annotations — don't "
                    "also pass them", InvalidArgumentError)
            # pp excluded (pipeline trainer executes those); sh capped
            # at stage 1 — the stage Engine can actually deliver
            process_mesh, planned_ann, cands = choose_strategy(
                model, batch_tokens=batch_tokens,
                per_device_bytes=per_device_bytes,
                example_inputs=example_inputs, allow_pp=False,
                allow_sh=1)
            annotations = planned_ann
            chosen = next(c for c in cands if c.get("chosen"))
            self.sharding_stage = int(chosen["sh"])
            batch_dim_mesh_axis = batch_dim_mesh_axis or "dp"
        else:
            enforce(plan is None,
                    f"plan must be None or 'auto', got {plan!r}",
                    InvalidArgumentError)
        self.process_mesh = process_mesh or ProcessMesh(
            shape=(len(jax.devices()),), dim_names=("dp",))
        self.batch_axis = batch_dim_mesh_axis or self.process_mesh.dim_names[0]
        self.annotations = annotations or {}
        self._prepared = False

    # -- prepare (plan + partition, engine.py prepare/_build) ------------

    def _place_state(self, state, opt_state):
        """Place a (state, opt_state) pair onto the engine's mesh per
        ``param_specs`` (annotated prepare) or replicated. Shared by
        :meth:`prepare` and :meth:`load` so a restore lands on EXACTLY
        the placements training used — a sharded engine must not
        silently come back replicated (reference Engine.load restores
        dist-attrs with the checkpoint)."""
        mesh = self.process_mesh.jax_mesh
        repl = NamedSharding(mesh, PartitionSpec())

        # normalize containers to plain dicts: nn.get_state hands
        # OrderedDicts, the checkpoint loader plain dicts — a mixed tree
        # breaks tree_map inside optimizer.update (dict vs OrderedDict
        # are different pytree node types) and a prepare/load mismatch
        # would silently retrace the compiled step
        def plain(tree):
            if isinstance(tree, dict):
                return {k: plain(v) for k, v in tree.items()}
            return tree

        state, opt_state = plain(state), plain(opt_state)
        stage1 = (self.sharding_stage >= 1
                  and dict(zip(self.process_mesh.dim_names,
                               self.process_mesh.shape)
                           ).get(self.batch_axis, 1) > 1)
        if not self.param_specs and not stage1:
            return (jax.device_put(state, repl),
                    jax.device_put(opt_state, repl))

        def pspec(name):
            return (self.param_specs or {}).get(name, PartitionSpec())

        # device_put shards numpy/host arrays directly — no jnp.asarray,
        # which would materialize the FULL array on one device first
        placed = {
            name: jax.device_put(arr, NamedSharding(mesh, pspec(name)))
            for name, arr in state["params"].items()
        }
        from ..optimizer import map_param_slots

        # optimizer slots mirror the params dict → same layouts; under
        # stage-1 ZeRO each slot additionally shards over the dp axis
        # on its first free divisible dim (sharding_optimizer.py's
        # param→rank assignment expressed as placement; the elementwise
        # update computes shard-locally, GSPMD gathers params for fwd)
        def slot_spec(name):
            base = pspec(name)
            if not stage1:
                return base
            return _insert_axis_spec(base, state["params"][name].shape,
                                     self.batch_axis,
                                     dict(zip(self.process_mesh.dim_names,
                                              self.process_mesh.shape))
                                     [self.batch_axis])

        slot_sh = map_param_slots(
            opt_state["slots"], state["params"],
            mirror_fn=lambda sub: type(sub)(
                (n, NamedSharding(mesh, slot_spec(n))) for n in sub),
            other_leaf_fn=lambda _: repl)
        opt_state = jax.tree_util.tree_map(
            jax.device_put, opt_state, {"step": repl, "slots": slot_sh})
        return ({"params": placed,
                 "buffers": jax.device_put(state["buffers"], repl)},
                opt_state)

    def prepare(self) -> None:
        dims = dict(zip(self.process_mesh.dim_names,
                        self.process_mesh.shape))
        enforce(dims.get("pp", 1) == 1,
                "Engine executes dp/mp (GSPMD) plans only — a pp>1 plan "
                "from choose_strategy must run through the pipeline "
                "trainer (paddle_tpu.parallel.hybrid / parallel.pipeline"
                "), which actually partitions stages. Engine placement "
                "would replicate params across pp and the planner's "
                "1/pp memory relief would not materialize.",
                InvalidArgumentError)
        mesh = self.process_mesh.jax_mesh
        state = nn.get_state(self.model)
        opt_state = self.optimizer.init(state["params"])
        batch_sh = NamedSharding(mesh, PartitionSpec(self.batch_axis))
        if self.annotations:
            # completion: one or two hints → a spec for every parameter;
            # placement seeds GSPMD, which completes the intermediates
            self.param_specs = complete_shardings(
                self.model, self.process_mesh, self.annotations,
                example_inputs=self.example_inputs)
        else:
            self.param_specs = None
        self._state, self._opt_state = self._place_state(state, opt_state)
        self._rng = jax.random.key(0)

        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer

        def step(state, opt_state, rng, inputs, labels):
            def compute_loss(params):
                out, new_state = nn.functional_call(
                    model, {"params": params, "buffers": state["buffers"]},
                    *inputs, rng=rng, training=True)
                loss = loss_fn(out, *labels)
                scaled = (optimizer.scale_loss(loss, opt_state)
                          if hasattr(optimizer, "scale_loss") else loss)
                return scaled, (loss, new_state["buffers"])

            (_, (loss, new_buffers)), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(state["params"])
            new_params, new_opt = optimizer.update(grads, opt_state, state["params"])

            def plain(tree):  # functional_call returns OrderedDicts;
                # the carried state (and out_shardings pytree) is plain
                if isinstance(tree, dict):
                    return {k: plain(v) for k, v in tree.items()}
                return tree

            return ({"params": new_params, "buffers": plain(new_buffers)},
                    new_opt, loss)

        self._batch_sh = batch_sh
        # pin the carried-state output shardings to the placements
        # _place_state chose — for EVERY engine, not just stage 1.
        # Without the pin the compiler is free to re-lay-out params and
        # slots after the first step (stage 1: gathers the slots and
        # un-does ZeRO; annotated engines under this jax: GSPMD drifts
        # params off param_specs, so a later save→load→fit would land on
        # different placements than the run it resumed and retrace)
        sharding_of = lambda t: jax.tree_util.tree_map(
            lambda a: a.sharding, t)
        self._step = jax.jit(
            step, donate_argnums=(0, 1),
            out_shardings=(sharding_of(self._state),
                           sharding_of(self._opt_state), None))

        def fwd(state, inputs):
            out, _ = nn.functional_call(model, state, *inputs, training=False)
            return out

        self._fwd = jax.jit(fwd)
        self._prepared = True

    def _shard_batch(self, arrs) -> Tuple:
        return tuple(
            jax.device_put(jnp.asarray(a), self._batch_sh) for a in arrs)

    # -- train/eval/predict (engine.py fit:…, evaluate, predict) ---------

    def fit(self, train_data: Iterable, epochs: int = 1,
            log_every: int = 0) -> List[float]:
        if not self._prepared:
            self.prepare()
        losses: List[float] = []
        step_no = 0
        for _ in range(epochs):
            for inputs, labels in train_data:
                ins = inputs if isinstance(inputs, (tuple, list)) else (inputs,)
                lbs = labels if isinstance(labels, (tuple, list)) else (labels,)
                self._rng, sub = jax.random.split(self._rng)
                self._state, self._opt_state, loss = self._step(
                    self._state, self._opt_state, sub,
                    self._shard_batch(ins), self._shard_batch(lbs))
                losses.append(float(loss))
                step_no += 1
                if log_every and step_no % log_every == 0:
                    print(f"[auto_parallel] step {step_no} loss {losses[-1]:.4f}")
        return losses

    def evaluate(self, data: Iterable, metric_fn: Optional[Callable] = None
                 ) -> float:
        if not self._prepared:
            self.prepare()
        total, n = 0.0, 0
        for inputs, labels in data:
            ins = inputs if isinstance(inputs, (tuple, list)) else (inputs,)
            lbs = labels if isinstance(labels, (tuple, list)) else (labels,)
            out = self._fwd(self._state, self._shard_batch(ins))
            if metric_fn is not None:
                total += float(metric_fn(out, *lbs))
            else:
                total += float(self.loss_fn(out, *(jnp.asarray(l) for l in lbs)))
            n += 1
        return total / max(n, 1)

    def predict(self, inputs):
        if not self._prepared:
            self.prepare()
        ins = inputs if isinstance(inputs, (tuple, list)) else (inputs,)
        return self._fwd(self._state, self._shard_batch(ins))

    # -- checkpoint (engine.py save/load surface) -------------------------

    def save(self, path: str) -> None:
        """Persist model + optimizer state AND the rng stream (reference
        Engine.save) — resumed training continues the same stochastic
        trajectory (dropout keys), not a fresh one."""
        from ..io.checkpoint import save_train_state

        enforce(self._prepared, "prepare()/fit() before save")
        save_train_state(path, self._state, opt_state=self._opt_state,
                         rng=self._rng)

    def load(self, path: str) -> None:
        """Restore a snapshot saved by :meth:`save`; arrays are placed
        back onto the engine's mesh with the SAME placements prepare()
        chose — ``param_specs`` placement for an annotated engine,
        replicated otherwise (reference Engine load restores dist-attrs;
        a sharded model restored replicated would OOM or silently train
        replicated at planner-scale sizes). The checkpoint holds full
        (unsharded) host arrays, so loading into an engine prepared on a
        DIFFERENT mesh or annotation set is a reshard: device_put lays
        each array out per the new engine's specs."""
        from ..io.checkpoint import load_train_state

        if not self._prepared:
            self.prepare()
        snap = load_train_state(path)
        self._state, self._opt_state = self._place_state(
            snap["state"], snap["opt"])
        self._rng = snap["rng"] if snap["rng"] is not None else self._rng

    # -- introspection ----------------------------------------------------

    def completion(self, example_inputs, example_labels) -> Dict[str, Any]:
        """What the reference's Completer decides by propagation, read
        back from the compiled executable: the shardings GSPMD chose for
        params and outputs."""
        if not self._prepared:
            self.prepare()
        ins = tuple(jnp.asarray(a) for a in (
            example_inputs if isinstance(example_inputs, (tuple, list))
            else (example_inputs,)))
        lbs = tuple(jnp.asarray(a) for a in (
            example_labels if isinstance(example_labels, (tuple, list))
            else (example_labels,)))
        lowered = self._step.lower(
            self._state, self._opt_state, self._rng,
            self._shard_batch(ins), self._shard_batch(lbs))
        compiled = lowered.compile()
        return {
            "input_shardings": compiled.input_shardings,
            "output_shardings": compiled.output_shardings,
        }

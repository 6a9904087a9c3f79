"""Fused Pallas kernels for the persistent hot-embedding tier.

The PAPER.md north star says "PHI sparse kernels lower to Pallas"; the
PR 6 tier left the warm path as three separate XLA ops — two bucket-row
gathers for the probe (ps/device_hash.py ``dynamic_map_lookup``), a row
gather for the pull and a unique/gather/update/scatter chain for the
push — each materializing its [n, ·] intermediates through HBM. This
module fuses them into two kernels (the GPUPS HashTable::get /
update_value analogues, optimizer.cuh.h one-thread-per-row shape):

- :func:`hot_probe_gather` — bucketized linear-probe lookup FUSED with
  the value-row gather: the probe's bucket lines and the matched row's
  value line are touched in one kernel pass, the [n, B] bucket
  intermediates never leave VMEM. Grid is (key-block × bank): with the
  map's NUMA-style banks each program loads ONE bank's bucket region
  and ONE bank's row block — the per-program VMEM footprint is
  ``map_bytes/banks + state_bytes/banks``, which is what makes the
  fused formulation fit on-chip at production capacities.
- :func:`hot_scatter_apply` — the push half: in-batch dedup'd gradients
  (the merge_grad unique+segment-sum, identical to
  ``cache_push_sparse``) feed a kernel that walks the touched rows
  once — read row, apply the f32-sealed CTR rule
  (ops/sparse_optimizer.py ``fused_row_update``, the ONE shared
  definition), write row — so only O(batch) rows cross HBM and the
  gathered/updated [n, width] intermediates never materialize.

STATUS: these kernels are proven in interpret mode only. Mosaic (jax
0.9.0 / libtpu 0.0.34, compiled for the v5e topology — see
tests/test_tpu_lowering.py, which pins each refusal) rejects all three:
``hot_probe_gather`` and ``hot_probe`` on the in-kernel ``jnp.take``
row gathers ("Shape mismatch in input, indices and output"),
``hot_scatter_apply`` on the scalar ``rows_ref[i]`` read from a VMEM
vector ("cannot statically prove that index in dimension 0 is a
multiple of …"); their BlockSpecs also hold whole table columns in
VMEM. So ``HotTierConfig.kernels="auto"`` resolves to the jnp
formulation on EVERY backend, and ``kernels="pallas"`` is an explicit
request: interpret mode off-TPU (the parity configuration), a loud
Mosaic compile error on the chip. Bit-parity contract: the kernels
share the hash math (``dynamic_probe_buckets``) and the rule math
(``fused_row_update``) with the jnp path by IMPORT, not by copy —
tests/test_hot_kernels.py pins Pallas(interpret) ≡ jnp ≡ the host
engines for adagrad and adam, unaligned n included.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.enforce import enforce
from .sparse_optimizer import fused_row_update, rule_state_dim

__all__ = ["hot_probe_gather", "hot_probe", "hot_scatter_apply",
           "resolve_hot_kernels"]


def resolve_hot_kernels(mode: str) -> bool:
    """Resolve HotTierConfig.kernels → use the Pallas kernels? "auto"
    is the jnp formulation on every backend — the one that compiles for
    the chip (module docstring STATUS); "pallas" is the explicit
    request for the kernels (interpret mode off-TPU, Mosaic on it);
    "jnp" names the reference path outright."""
    enforce(mode in ("auto", "pallas", "jnp"),
            f"kernels must be 'auto', 'pallas' or 'jnp', got {mode!r}")
    return mode == "pallas"


def _interp(interpret: Optional[bool]) -> bool:
    # trace-time config (a python bool/None, never a tracer)
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _probe_body(maph, mapl, mapr, seed, hi, lo, probe_buckets: int,
                nbuckets: int, banks: int, bank: Optional[jax.Array]):
    """The in-kernel probe: identical hash/compare/select math as
    ``dynamic_map_lookup`` (shared ``dynamic_probe_buckets``), operating
    on ONE bank's bucket region (``bank`` = this program's bank id, or
    None for the unbanked full region)."""
    from ..ps.device_hash import dynamic_probe_buckets

    if bank is None:
        buckets = dynamic_probe_buckets(nbuckets, hi, lo, seed,
                                        probe_buckets, banks)
    else:
        # region-relative: the refs hold only this bank's [nbpb, B]
        # slice, so probe with the LOCAL window (banks=1 of the region)
        buckets = dynamic_probe_buckets(nbuckets // banks, hi, lo, seed,
                                        probe_buckets, 1)
    found = jnp.full(hi.shape, -1, jnp.int32)
    for b in buckets:
        bh = jnp.take(maph, b, axis=0)      # [bn, B] — stays in VMEM
        bl = jnp.take(mapl, b, axis=0)
        br = jnp.take(mapr, b, axis=0)
        match = (bh == hi[:, None]) & (bl == lo[:, None]) & (br >= 0)
        hit = jnp.max(jnp.where(match, br, -1), axis=1)
        found = jnp.where(found >= 0, found, hit)
    return found


def _bank_of_dev(hi: jax.Array, lo: jax.Array, banks: int) -> jax.Array:
    from ..ps.device_hash import _BANK_SEED, _mix32

    return (_mix32(hi, lo, jnp.uint32(_BANK_SEED))
            & jnp.uint32(banks - 1)).astype(jnp.int32)


# graftlint: hot-path
def hot_probe_gather(
    map_state: Dict[str, jax.Array],
    keys_hi: jax.Array,   # [n] uint32
    keys_lo: jax.Array,   # [n] uint32
    tier_state: Dict[str, jax.Array],
    *,
    probe_buckets: int,
    banks: int = 1,
    block: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused probe+gather: keys → (rows [n] i32, −1 = missing;
    pulled [n, 1+embedx_dim] f32, zeros for missing rows) in ONE kernel
    pass. Bit-identical to ``dynamic_map_lookup`` + ``cache_pull``.

    With ``banks > 1`` the grid is (key-block, bank): each program sees
    one bank's bucket region and one bank's row block, and only lanes
    whose key hashes to that bank contribute (the tier's allocation
    contract places a key's row inside its bank's row block, so the
    bank-local gather is total). Output blocks are revisited across the
    bank dimension and merged with ``where`` — the standard Pallas
    grid-reduction pattern.
    """
    n = keys_hi.shape[0]
    nbuckets, bslots = map_state["row"].shape
    C = tier_state["embed_w"].shape[0]
    xd = tier_state["embedx_w"].shape[1]
    enforce(C % banks == 0 and nbuckets % banks == 0,
            f"capacity {C} / nbuckets {nbuckets} must divide banks {banks}")
    Cb = C // banks
    nbpb = nbuckets // banks
    seed2d = map_state["seed"].reshape(1, 1)
    bn = min(block, n)
    grid = (pl.cdiv(n, bn), banks)

    def kern(seed_ref, hi_ref, lo_ref, maph_ref, mapl_ref, mapr_ref,
             ew_ref, xw_ref, o_rows, o_pull):
        bank = pl.program_id(1)
        hi = hi_ref[...]
        lo = lo_ref[...]
        seed = seed_ref[0, 0]
        found = _probe_body(maph_ref[...], mapl_ref[...], mapr_ref[...],
                            seed, hi, lo, probe_buckets, nbuckets, banks,
                            bank if banks > 1 else None)
        # bank-local gather: rows of this bank live in [bank*Cb, ..)
        loc = found - bank * Cb if banks > 1 else found
        safe = jnp.clip(loc, 0, Cb - 1)
        pulled = jnp.concatenate(
            [jnp.take(ew_ref[...], safe, axis=0),
             jnp.take(xw_ref[...], safe, axis=0)], axis=1)
        pulled = jnp.where((found >= 0)[:, None], pulled, 0.0)
        if banks > 1:
            mine = _bank_of_dev(hi, lo, banks) == bank
            # revisit-merge: bank 0 initializes, later banks fold in
            @pl.when(bank == 0)
            def _():
                o_rows[...] = jnp.where(mine, found, -1)
                o_pull[...] = jnp.where(mine[:, None], pulled, 0.0)

            @pl.when(bank > 0)
            def _():
                o_rows[...] = jnp.where(mine, found, o_rows[...])
                o_pull[...] = jnp.where(mine[:, None], pulled, o_pull[...])
        else:
            o_rows[...] = found
            o_pull[...] = pulled

    out = pl.pallas_call(
        kern,
        name="hot_probe_gather",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, b: (0, 0)),            # seed
            pl.BlockSpec((bn,), lambda i, b: (i,)),               # hi
            pl.BlockSpec((bn,), lambda i, b: (i,)),               # lo
            pl.BlockSpec((nbpb, bslots), lambda i, b: (b, 0)),    # map hi
            pl.BlockSpec((nbpb, bslots), lambda i, b: (b, 0)),    # map lo
            pl.BlockSpec((nbpb, bslots), lambda i, b: (b, 0)),    # map row
            pl.BlockSpec((Cb, 1), lambda i, b: (b, 0)),           # embed_w
            pl.BlockSpec((Cb, xd), lambda i, b: (b, 0)),          # embedx_w
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i, b: (i,)),
            pl.BlockSpec((bn, 1 + xd), lambda i, b: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n, 1 + xd), jnp.float32),
        ],
        interpret=_interp(interpret),
    )(seed2d, keys_hi.astype(jnp.uint32), keys_lo.astype(jnp.uint32),
      map_state["hi"], map_state["lo"], map_state["row"],
      tier_state["embed_w"], tier_state["embedx_w"])
    return out[0], out[1]


# graftlint: hot-path
def hot_probe(
    map_state: Dict[str, jax.Array],
    keys_hi: jax.Array,
    keys_lo: jax.Array,
    *,
    probe_buckets: int,
    banks: int = 1,
    block: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Probe-only kernel (rows [n] i32, −1 = missing): the sharded
    tier's LOCAL half — each device resolves its batch slice against
    the replicated map, then the row exchange (not the gather) crosses
    chips, so there is nothing to fuse the gather into here."""
    n = keys_hi.shape[0]
    nbuckets, bslots = map_state["row"].shape
    enforce(nbuckets % banks == 0,
            f"nbuckets {nbuckets} must divide banks {banks}")
    nbpb = nbuckets // banks
    seed2d = map_state["seed"].reshape(1, 1)
    bn = min(block, n)
    grid = (pl.cdiv(n, bn), banks)

    def kern(seed_ref, hi_ref, lo_ref, maph_ref, mapl_ref, mapr_ref,
             o_rows):
        bank = pl.program_id(1)
        hi = hi_ref[...]
        lo = lo_ref[...]
        found = _probe_body(maph_ref[...], mapl_ref[...], mapr_ref[...],
                            seed_ref[0, 0], hi, lo, probe_buckets,
                            nbuckets, banks, bank if banks > 1 else None)
        if banks > 1:
            mine = _bank_of_dev(hi, lo, banks) == bank
            @pl.when(bank == 0)
            def _():
                o_rows[...] = jnp.where(mine, found, -1)

            @pl.when(bank > 0)
            def _():
                o_rows[...] = jnp.where(mine, found, o_rows[...])
        else:
            o_rows[...] = found

    return pl.pallas_call(
        kern,
        name="hot_probe",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, b: (0, 0)),
            pl.BlockSpec((bn,), lambda i, b: (i,)),
            pl.BlockSpec((bn,), lambda i, b: (i,)),
            pl.BlockSpec((nbpb, bslots), lambda i, b: (b, 0)),
            pl.BlockSpec((nbpb, bslots), lambda i, b: (b, 0)),
            pl.BlockSpec((nbpb, bslots), lambda i, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i, b: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=_interp(interpret),
    )(seed2d, keys_hi.astype(jnp.uint32), keys_lo.astype(jnp.uint32),
      map_state["hi"], map_state["lo"], map_state["row"])


_COLS = ("show", "click", "embed_w", "embed_state", "embedx_w",
         "embedx_state", "has_embedx")


# graftlint: hot-path
def hot_scatter_apply(
    state: Dict[str, jax.Array],
    rows: jax.Array,    # [n] tier rows (may repeat; ≥ C = dropped)
    grads: jax.Array,   # [n, 1+dim] embed_g ++ embedx_g
    shows: jax.Array,   # [n]
    clicks: jax.Array,  # [n]
    cfg,                # embedding_cache.CacheConfig
    *,
    interpret: Optional[bool] = None,
) -> Dict[str, jax.Array]:
    """Fused push: merge_grad dedup (unique + segment-sum — EXACTLY
    ``cache_push_sparse``'s prologue, so the f32 merge association is
    identical) → ONE kernel that walks the deduped rows, applies the
    sealed CTR rule (``fused_row_update`` per row — the optimizer.cuh.h
    one-thread-per-row shape) and scatters the updated row back in
    place. Only the touched rows cross HBM; the gathered/updated
    [n, width] intermediates of the jnp path never materialize.

    Drop-in ``cache_push`` replacement with sparse-mode semantics —
    bit-identical to ``cache_push_sparse`` with the jnp rule path
    (tests/test_hot_kernels.py pins it for adagrad, std_adagrad and
    adam, unaligned n included)."""
    from ..ps.embedding_cache import merge_sparse_grads

    n = rows.shape[0]
    C = state["embed_w"].shape[0]
    dim = state["embedx_w"].shape[1]
    sgd = cfg.sgd

    # merge_grad — the ONE shared dedup (bit-parity with cache_push_sparse)
    uniq, show_sum, click_sum, g = merge_sparse_grads(rows, grads, shows,
                                                      clicks, C)

    es = rule_state_dim(cfg.embed_rule, 1)
    xs = rule_state_dim(cfg.embedx_rule, dim)
    enforce(state["embed_state"].shape[1] == es
            and state["embedx_state"].shape[1] == xs,
            f"optimizer-state width mismatch: embed_state "
            f"{state['embed_state'].shape} vs {es}, embedx_state "
            f"{state['embedx_state'].shape} vs {xs}")
    # zero-width optimizer state (naive rule) → one dummy column through
    # the kernel, original restored after (the ctr_sparse_rows pattern)
    kstate = dict(state)
    if es == 0:
        kstate["embed_state"] = jnp.zeros((C, 1), jnp.float32)
    if xs == 0:
        kstate["embedx_state"] = jnp.zeros((C, 1), jnp.float32)
    widths = {k: kstate[k].shape[1] if kstate[k].ndim == 2 else None
              for k in _COLS}

    upd = functools.partial(
        fused_row_update, embed_rule=cfg.embed_rule,
        embedx_rule=cfg.embedx_rule, dim=dim, lr=sgd.learning_rate,
        initial_g2sum=sgd.initial_g2sum, wmin=sgd.weight_bounds[0],
        wmax=sgd.weight_bounds[1], beta1=sgd.beta1, beta2=sgd.beta2,
        eps=sgd.ada_epsilon, nonclk_coeff=cfg.nonclk_coeff,
        click_coeff=cfg.click_coeff, embedx_threshold=cfg.embedx_threshold,
        create_applies_grad=cfg.create_applies_grad)

    def kern(*refs):
        in_refs = refs[:7]
        rows_ref, ds_ref, dc_ref, ge_ref, gx_ref = refs[7:12]
        out_refs = refs[12:]
        # untouched rows round-trip bit-for-bit: start from the input
        for i_ref, o_ref in zip(in_refs, out_refs):
            o_ref[...] = i_ref[...]

        def body(i, carry):
            r = rows_ref[i]

            # sentinel C (padding / missing) AND negatives drop — the
            # jnp path's scatter ``mode="drop"`` semantics
            @pl.when(jnp.logical_and(r >= 0, r < C))
            def _():
                rr = jnp.clip(r, 0, C - 1)
                cols = []
                for ref in in_refs:
                    if len(ref.shape) == 1:
                        cols.append(ref[pl.ds(rr, 1)])
                    else:
                        cols.append(ref[pl.ds(rr, 1), :])
                outs = upd(*cols, ds_ref[pl.ds(i, 1)], dc_ref[pl.ds(i, 1)],
                           ge_ref[pl.ds(i, 1), :], gx_ref[pl.ds(i, 1), :])
                for o_ref, val in zip(out_refs, outs):
                    if len(o_ref.shape) == 1:
                        o_ref[pl.ds(rr, 1)] = val
                    else:
                        o_ref[pl.ds(rr, 1), :] = val
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    def col_spec(k):
        w = widths[k]
        if w is None:
            return pl.BlockSpec((C,), lambda: (0,))
        return pl.BlockSpec((C, w), lambda: (0, 0))

    state_specs = [col_spec(k) for k in _COLS]
    out_shapes = [jax.ShapeDtypeStruct(kstate[k].shape, kstate[k].dtype)
                  for k in _COLS]
    out = pl.pallas_call(
        kern,
        name="hot_scatter_apply",
        grid=(),
        in_specs=state_specs + [
            pl.BlockSpec((n,), lambda: (0,)),        # uniq rows
            pl.BlockSpec((n,), lambda: (0,)),        # show deltas
            pl.BlockSpec((n,), lambda: (0,)),        # click deltas
            pl.BlockSpec((n, 1), lambda: (0, 0)),    # embed grads
            pl.BlockSpec((n, dim), lambda: (0, 0)),  # embedx grads
        ],
        out_specs=state_specs,
        out_shape=out_shapes,
        interpret=_interp(interpret),
    )(*[kstate[k] for k in _COLS], uniq, show_sum, click_sum,
      g[:, :1], g[:, 1:])
    new = dict(zip(_COLS, out))
    if es == 0:
        new["embed_state"] = state["embed_state"]
    if xs == 0:
        new["embedx_state"] = state["embedx_state"]
    return new

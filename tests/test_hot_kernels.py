"""Fused Pallas hot-tier kernels (ops/hot_kernels.py): bit-parity of
the Pallas(interpret) kernels against the jnp reference formulations —
probe+gather vs ``dynamic_map_lookup`` + ``cache_pull``, scatter+apply
vs ``cache_push_sparse`` — across the rule family (adagrad, std_adagrad,
adam, naive), unaligned n, banked maps, duplicate/sentinel rows and
post-mutation map states. Tier-level parity (eviction churn, checkpoint
/restore, the RPC-only oracle) rides tests/test_hot_tier.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.hot_kernels import (hot_probe, hot_probe_gather,
                                        hot_scatter_apply,
                                        resolve_hot_kernels)
from paddle_tpu.ops.sparse_optimizer import rule_state_dim
from paddle_tpu.ps.device_hash import (DynamicDeviceKeyMap,
                                       dynamic_map_lookup, split_keys)
from paddle_tpu.ps.embedding_cache import (CacheConfig, cache_pull,
                                           cache_push_sparse)
from paddle_tpu.ps.sgd_rule import SGDRuleConfig


def _banked_map(C, banks, keys, rng):
    """Map + per-bank row allocation (the tier's placement contract:
    a key's row lives inside its bank's contiguous row block)."""
    m = DynamicDeviceKeyMap(C, banks=banks)
    Cb = C // banks
    bk = m.bank_of(keys)
    rows = np.zeros(len(keys), np.int32)
    nxt = [0] * banks
    for i, b in enumerate(bk):
        rows[i] = b * Cb + nxt[b]
        nxt[b] += 1
    m.insert(keys, rows)
    return m, rows


def _tier_state(C, xd, rng, es=1, xs=1):
    return {
        "show": jnp.asarray(np.abs(rng.normal(size=C)).astype(np.float32)),
        "click": jnp.asarray(np.abs(rng.normal(size=C)).astype(np.float32)),
        "embed_w": jnp.asarray(rng.normal(size=(C, 1)).astype(np.float32)),
        "embed_state": jnp.asarray(
            np.abs(rng.normal(size=(C, es))).astype(np.float32)),
        "embedx_w": jnp.asarray(rng.normal(size=(C, xd)).astype(np.float32)),
        "embedx_state": jnp.asarray(
            np.abs(rng.normal(size=(C, xs))).astype(np.float32)),
        "has_embedx": jnp.asarray((rng.random(C) > 0.5).astype(np.float32)),
    }


@pytest.mark.parametrize("banks", [1, 4])
def test_probe_gather_matches_jnp_reference(banks):
    """Fused probe+gather ≡ dynamic_map_lookup + cache_pull, bitwise —
    unaligned n (not a block multiple), missing keys pulling zeros."""
    rng = np.random.default_rng(0)
    C, xd = 256, 8
    keys = np.unique(rng.integers(1, 2**63, 300).astype(np.uint64))[:120]
    m, rows = _banked_map(C, banks, keys, rng)
    state = _tier_state(C, xd, rng)
    # 157 probes = resident + absent, NOT a multiple of the 64 block
    probe = np.concatenate([keys,
                            rng.integers(1, 2**63, 37).astype(np.uint64)])
    hi, lo = split_keys(probe)
    hi, lo = jnp.asarray(hi), jnp.asarray(lo)
    ms = m.device_state()
    ref_rows = dynamic_map_lookup(ms, hi, lo, m.probe_buckets, banks)
    ref_pull = cache_pull(state, jnp.where(ref_rows >= 0, ref_rows, C))
    krows, kpull = hot_probe_gather(ms, hi, lo, state,
                                    probe_buckets=m.probe_buckets,
                                    banks=banks, block=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(krows), np.asarray(ref_rows))
    np.testing.assert_array_equal(np.asarray(kpull), np.asarray(ref_pull))
    # the resident keys actually resolved (not a trivially-all-miss run)
    assert (np.asarray(krows)[:len(keys)] == rows).all()
    assert (np.asarray(krows)[len(keys):] == -1).all()

    prows = hot_probe(ms, hi, lo, probe_buckets=m.probe_buckets,
                      banks=banks, block=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(prows), np.asarray(ref_rows))


@pytest.mark.parametrize("banks", [1, 4])
def test_probe_gather_after_mutation_and_rebuild(banks):
    """Evict/insert churn (incremental device patches) and a grow
    rebuild (full re-upload, new probe seed) — the kernel probes the
    SAME device state the jnp path does, so parity must survive both."""
    rng = np.random.default_rng(1)
    C, xd = 256, 4
    keys = np.unique(rng.integers(1, 2**63, 300).astype(np.uint64))[:96]
    m, rows = _banked_map(C, banks, keys, rng)
    state = _tier_state(C, xd, rng)
    hi, lo = split_keys(keys)
    hi, lo = jnp.asarray(hi), jnp.asarray(lo)

    def check():
        ms = m.device_state()
        ref = dynamic_map_lookup(ms, hi, lo, m.probe_buckets, banks)
        got, _ = hot_probe_gather(ms, hi, lo, state,
                                  probe_buckets=m.probe_buckets,
                                  banks=banks, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(ref), m.lookup_host(keys))

    check()
    m.remove(keys[::3])          # tombstones → incremental patches
    check()
    m._rebuild(grow=True)        # reseed + grow → full re-upload
    check()


@pytest.mark.parametrize("rule", ["adagrad", "std_adagrad", "adam", "naive"])
def test_scatter_apply_matches_jnp_reference(rule):
    """Fused scatter+apply ≡ cache_push_sparse (jnp rule path), bitwise:
    the full rule family, duplicate rows (merge association pinned by
    the shared unique/segment-sum prologue), sentinel rows dropped,
    unaligned n."""
    rng = np.random.default_rng(2)
    C, xd, n = 128, 8, 101  # prime n — no alignment luck
    cfg = CacheConfig(capacity=C, embedx_dim=xd, embed_rule=rule,
                      embedx_rule=rule, sgd=SGDRuleConfig(),
                      pallas_update=False, push_mode="sparse")
    es, xs = rule_state_dim(rule, 1), rule_state_dim(rule, xd)
    state = _tier_state(C, xd, rng, es=es, xs=xs)
    if rule == "adam":
        # beta-power columns must be in (0, 1) like real rows
        st = np.array(state["embedx_state"])
        st[:, 2 * xd:] = 0.9
        state["embedx_state"] = jnp.asarray(st)
        est = np.array(state["embed_state"])
        est[:, 2:] = 0.9
        state["embed_state"] = jnp.asarray(est)
    rows = np.concatenate([rng.integers(0, C, n - 16),
                           rng.integers(0, C, 8),  # duplicates likely
                           np.full(8, C)])         # sentinel → dropped
    rows = jnp.asarray(rows.astype(np.int32))
    grads = jnp.asarray(rng.normal(size=(n, 1 + xd)).astype(np.float32))
    shows = jnp.ones(n, jnp.float32)
    clicks = jnp.asarray((rng.random(n) > 0.7).astype(np.float32))
    ref = cache_push_sparse(state, rows, grads, shows, clicks, cfg)
    got = hot_scatter_apply(state, rows, grads, shows, clicks, cfg,
                            interpret=True)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]),
                                      err_msg=f"{rule}: column {k}")
    # the update actually landed somewhere (not a trivially-equal no-op)
    assert not np.array_equal(np.asarray(got["embed_w"]),
                              np.asarray(state["embed_w"]))


def test_scatter_apply_under_jit_and_donation():
    """The kernel composes into a jitted step with the tier-state
    donation the trainer uses."""
    rng = np.random.default_rng(3)
    C, xd, n = 64, 4, 32
    cfg = CacheConfig(capacity=C, embedx_dim=xd, push_mode="sparse",
                      pallas_update=False)
    state = _tier_state(C, xd, rng)
    rows = jnp.asarray(rng.integers(0, C, n).astype(np.int32))
    grads = jnp.asarray(rng.normal(size=(n, 1 + xd)).astype(np.float32))
    shows = jnp.ones(n, jnp.float32)
    clicks = jnp.zeros(n, jnp.float32)
    ref = cache_push_sparse(state, rows, grads, shows, clicks, cfg)

    @jax.jit
    def step(st):
        return hot_scatter_apply(st, rows, grads, shows, clicks, cfg,
                                 interpret=True)

    got = step(state)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(ref[k]))


def test_bank_membership_stable_across_rebuilds():
    """bank_of is a FIXED hash: reseed and grow rebuilds relocate
    buckets but never move a key between banks (the tier's row blocks
    depend on it)."""
    rng = np.random.default_rng(4)
    m = DynamicDeviceKeyMap(256, banks=8)
    keys = np.unique(rng.integers(1, 2**63, 300).astype(np.uint64))[:128]
    before = m.bank_of(keys)
    m.insert(keys, np.arange(len(keys), dtype=np.int32))
    m._rebuild(grow=False)   # reseed
    m._rebuild(grow=True)    # grow
    np.testing.assert_array_equal(m.bank_of(keys), before)
    np.testing.assert_array_equal(m.lookup_host(keys),
                                  np.arange(len(keys), dtype=np.int32))
    # banked probe never resolves a key through another bank's region:
    # the in-graph lookup agrees with the host mirror on every key
    hi, lo = split_keys(keys)
    got = np.asarray(dynamic_map_lookup(m.device_state(), jnp.asarray(hi),
                                        jnp.asarray(lo), m.probe_buckets,
                                        m.banks))
    np.testing.assert_array_equal(got, m.lookup_host(keys))


def test_resolve_hot_kernels(monkeypatch):
    assert resolve_hot_kernels("pallas") is True
    assert resolve_hot_kernels("jnp") is False
    # "auto" is the formulation that compiles for the chip, on every
    # backend (tests/test_tpu_lowering.py pins why that is not pallas)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_hot_kernels("auto") is False
    with pytest.raises(Exception, match="kernels"):
        resolve_hot_kernels("cuda")

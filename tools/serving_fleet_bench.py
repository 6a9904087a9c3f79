"""Serving-fleet bench (ISSUE 15 acceptance → SERVING_FLEET.json).

Drives the REAL fleet end to end — an HA training cluster, N≥3
:class:`ServingReplica` members (each: oplog-subscribed replica +
read-only hot tier + micro-batching frontend) behind a
:class:`ServingRouter` with bounded-load CH affinity and hedging, a
:class:`ServingFleet` lease watcher, and a :class:`RolloutManager` —
under an **open-loop** traffic replay (arrivals scheduled on the wall
clock at a target rate, submitted whether or not earlier requests
finished — the load shape that actually exposes tail collapse; a
closed loop self-throttles around it). Phases:

0. **single-member reference** — the SAME open-loop driver against a
   ONE-member fleet at the steady rate: the apples-to-apples p99
   baseline for the "fleet p99 within 2× of single-replica" prong.
   The committed SERVING.json p99 is a closed-loop number from a
   different host generation (2 cores then, 1 now —
   cross-record ratios are not comparable, same-box re-measurement
   is), so the fleet tax must be measured against a same-box,
   same-driver single member.
1. **steady** — warm replay at ``SFB_RATE_QPS`` (default 1.15× the
   committed SERVING.json qps): the LATENCY arm — zero errors, hedge
   rate bounded, p99 compared against arm 0.
2. **saturation** — replay at ``SFB_SAT_QPS`` (default 2.6× the
   committed baseline): the CAPACITY arm — open-loop arrivals near the
   fleet's ceiling, queues form, batches grow, and the achieved rate
   IS the aggregate throughput (read the steady arm for tails). With
   ``SFB_SINGLE=1`` the bench also re-measures the single-replica
   CLOSED-loop ceiling on this host via tools/serving_bench.run() so
   the committed artifact carries every baseline the acceptance names.
2. **kill-replica chaos** — mid-replay, one member dies SIGKILL-style
   (frontend dead, lease left to expire); the router reroutes its
   traffic and the lease watch removes it. Gate: ZERO request errors.
3. **draining restart** — a member is drained (eject → finish
   in-flight → graceful detach) and a fresh one joins WARM mid-replay.
   Gate: ZERO request errors.
4. **join miss curves** — a warm-handoff join vs a cold join, each
   serving the same replayed chunk; per-chunk tier-miss curves. Gate:
   warm misses < cold misses (the handoff kills the cold-miss storm).
5. **canary → promote → rollback** — a traffic chunk under a canary
   band (split counted per version and checked against the
   deterministic band predicate), promote to N+1 fleet-wide, then roll
   back; gate: version N restored digest-identical on EVERY member.

Standalone: prints exactly ONE JSON line (driver contract). Env knobs:
SFB_KEYS (population, 20k), SFB_REPLICAS (3), SFB_BATCH (64),
SFB_RATE_QPS (0 = derive from SERVING.json), SFB_STEADY (steady-phase
requests, 4000), SFB_CHUNK (chaos/join/canary chunk, 1500), SFB_DIM
(embedx, 8), SFB_DELAY_US (coalesce window, 2000). Shared-host note:
ambient load on the 2-core CI box moves p99 2-3×; the ci.sh gate
asserts the error/ordering invariants (zero errors, warm<cold, hedge
bound) and retries once — the committed SERVING_FLEET.json is a
quiet-host run that also meets the throughput/latency acceptance.
"""

import json
import os
import queue
import sys
import threading
import time

METRIC = "serving_fleet_agg_qps"


def run() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    import random as _random

    from paddle_tpu.io.fs import crc32c
    from paddle_tpu.ps import ha
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.hot_tier import HotEmbeddingTier, HotTierConfig
    from paddle_tpu.ps.sgd_rule import SGDRuleConfig
    from paddle_tpu.ps.table import TableConfig
    from paddle_tpu.serving import (CachedLookup, DenseModel, FleetConfig,
                                    FleetMember, FrontendConfig,
                                    RolloutManager, RouterConfig,
                                    ServingFleet, ServingFrontend,
                                    ServingReplica, ServingRouter)

    S, D = 8, 4
    xd = int(os.environ.get("SFB_DIM", 8))
    n_keys = int(float(os.environ.get("SFB_KEYS", 20_000)))
    n_replicas = int(os.environ.get("SFB_REPLICAS", 3))
    max_batch = int(os.environ.get("SFB_BATCH", 64))
    n_steady = int(float(os.environ.get("SFB_STEADY", 4000)))
    n_chunk = int(float(os.environ.get("SFB_CHUNK", 1500)))
    delay_us = int(os.environ.get("SFB_DELAY_US", 4000))
    rate_env = float(os.environ.get("SFB_RATE_QPS", 0))
    sat_env = float(os.environ.get("SFB_SAT_QPS", 0))
    with_single = os.environ.get("SFB_SINGLE", "0") == "1"

    block_shift = 6
    blocks = n_keys >> block_shift

    # single-replica baseline (the committed SERVING.json)
    base_qps, base_p99 = 0.0, 0.0
    sj = os.path.join(repo, "SERVING.json")
    if os.path.exists(sj):
        with open(sj) as f:
            rec = json.load(f)
        base_qps = float(rec.get("warm", {}).get("qps", 0.0))
        base_p99 = float(rec.get("warm", {}).get("request_ms", {})
                         .get("p99_ms", 0.0))
    rate_qps = rate_env if rate_env > 0 else max(1.15 * base_qps, 1000.0)
    sat_qps = sat_env if sat_env > 0 else max(2.6 * base_qps, 2000.0)

    # optional same-box single-replica re-measurement (committed-run
    # mode): the SERVING.json record may predate a host change, so the
    # capacity comparison re-baselines on THIS machine
    single_same_box = None
    if with_single:
        import tools.serving_bench as _sb

        saved = {k: os.environ.get(k) for k in ("SB_REQUESTS", "SB_PROBES")}
        os.environ["SB_REQUESTS"] = os.environ.get("SFB_SINGLE_REQS",
                                                   "2000")
        os.environ["SB_PROBES"] = "5"
        try:
            srec = _sb.run()
            single_same_box = {
                "qps": srec["warm"]["qps"],
                "p99_ms": srec["warm"]["request_ms"]["p99_ms"],
                "via": "tools/serving_bench.run() on this host",
            }
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    rng = np.random.default_rng(0)
    cfg = TableConfig(shard_num=8, accessor_config=AccessorConfig(
        embedx_dim=xd, embedx_threshold=0.0,
        sgd=SGDRuleConfig(initial_range=0.01)))

    with ha.HACluster(num_shards=1, replication=1, sync=False) as cluster:
        train_cli = cluster.client()
        train_cli.create_sparse_table(0, cfg)
        keys = np.arange(n_keys, dtype=np.uint64)
        width = None
        t0 = time.perf_counter()
        for lo in range(0, n_keys, 1 << 15):
            kc = keys[lo:lo + (1 << 15)]
            train_cli.pull_sparse(0, kc)
            if width is None:
                width = train_cli._dims(0)[1]
            push = np.zeros((len(kc), width), np.float32)
            push[:, 1] = 1.0
            push[:, 3:] = 0.01 * rng.standard_normal(
                (len(kc), width - 3)).astype(np.float32)
            train_cli.push_sparse(0, kc, push)
        preload_s = time.perf_counter() - t0

        # one shared jitted MLP head; per-member params holders
        x_dim = S * (1 + xd) + D
        flat_dim = x_dim * 16 + 16 + 16 + 1
        rngp = np.random.default_rng(7)
        flat_v1 = 0.1 * rngp.standard_normal(flat_dim).astype(np.float32)
        flat_v2 = flat_v1 + np.float32(0.01)

        def unravel(flat):
            i = 0
            w1 = flat[i:i + x_dim * 16].reshape(x_dim, 16); i += x_dim * 16
            b1 = flat[i:i + 16]; i += 16
            w2 = flat[i:i + 16].reshape(16, 1); i += 16
            b2 = flat[i:i + 1]
            return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}

        def _mlp(p, emb, dense):
            x = jnp.concatenate([emb.reshape(emb.shape[0], -1), dense],
                                axis=1)
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            return (h @ p["w2"] + p["b2"]).reshape(-1)

        infer_jit = jax.jit(_mlp)

        def make_member():
            rep = ServingReplica(cluster.store, cluster.job_id, shard=0,
                                 hb_interval=0.05, hb_ttl=0.4)
            serve = rep.client()
            view = rep.serve_view(0, cfg, client=serve)
            prim = cluster.primary(0)
            deadline = time.perf_counter() + 60
            while True:
                dg = cluster.digests(0, 0).get(prim.endpoint)
                if dg is not None and dg == serve.digest(0)[0]:
                    break
                if time.perf_counter() > deadline:
                    raise TimeoutError("replica never caught up")
                time.sleep(0.02)
            tier = HotEmbeddingTier(view, HotTierConfig(
                capacity=1 << int(np.ceil(np.log2(n_keys * 1.8))),
                create_on_miss=False))
            lookup = CachedLookup(tier, replica=rep,
                                  freshness_budget_s=30.0)
            holder = {}
            model = DenseModel(
                unravel, flat_v1,
                sink=lambda p: holder.__setitem__(
                    "p", jax.device_put(p)))

            def infer(emb, dense):
                B = emb.shape[0]
                Bp = 1 << (max(B, 1) - 1).bit_length()
                if Bp != B:
                    emb = np.concatenate(
                        [emb, np.zeros((Bp - B,) + emb.shape[1:],
                                       emb.dtype)])
                    dense = np.concatenate(
                        [dense, np.zeros((Bp - B, dense.shape[1]),
                                         dense.dtype)])
                return np.asarray(infer_jit(holder["p"], emb, dense))[:B]

            fe = ServingFrontend(lookup, infer=infer,
                                 config=FrontendConfig(
                                     max_batch=max_batch,
                                     max_delay_us=delay_us,
                                     queue_cap=4096,
                                     default_deadline_ms=2000.0),
                                 replica_label=rep.endpoint)
            # compile every pow-2 bucket NOW (both jits): warm traffic
            # must never compile
            Bp = 1
            while Bp <= max_batch:
                infer(np.zeros((Bp, S, 1 + xd), np.float32),
                      np.zeros((Bp, D), np.float32))
                lookup.lookup(keys[: Bp * S])
                Bp <<= 1
            tier.drop()   # compile priming polluted residency: restart cold
            return FleetMember(rep, lookup, fe, model=model)

        # hedge floor 10 ms: on a batching frontend the coalesce window
        # IS most of the latency — hedging below it duplicates healthy
        # requests (measured: p95-budget hedging at a 4 ms window ran a
        # 13% hedge rate, all losers)
        router = ServingRouter(RouterConfig(block_shift=block_shift,
                                            hedge_default_ms=25.0,
                                            hedge_floor_ms=10.0),
                               rng=_random.Random(0))
        fleet = ServingFleet(cluster.store, cluster.job_id, make_member,
                             router,
                             config=FleetConfig(poll_s=0.05,
                                                warm_chunk=4096,
                                                max_replicas=16)).start()
        rollout = RolloutManager(lambda: fleet.members(), router)
        fleet.rollout = rollout
        rollout.register_baseline(flat_v1)

        # -- open-loop replay machinery ---------------------------------
        def gen_requests(n, rblocks=None, seed=1):
            g = np.random.default_rng(seed)
            bs = g.integers(0, blocks, n) if rblocks is None else \
                g.choice(rblocks, n)
            reqs = []
            for b in bs:
                base = int(b) << block_shift
                ks = (base + g.integers(0, 1 << block_shift, S)).astype(
                    np.uint64)
                reqs.append((int(b), ks,
                             g.standard_normal(D).astype(np.float32)))
            return reqs

        def gen_cover_requests(seed=2):
            """One request per (block, key-octet): tiles EVERY key of
            every block exactly once — the priming pass that makes the
            steady arm a genuinely warm measurement (random draws leave
            ~3/4 of each block cold and the arm measures miss RPCs, not
            routing)."""
            g = np.random.default_rng(seed)
            reqs = []
            per = (1 << block_shift) // S
            for b in range(blocks):
                base = b << block_shift
                perm = g.permutation(1 << block_shift)
                for j in range(per):
                    ks = (base + perm[j * S:(j + 1) * S]).astype(np.uint64)
                    reqs.append((b, ks,
                                 g.standard_normal(D).astype(np.float32)))
            g.shuffle(reqs)
            return reqs

        def replay(reqs, rate, collectors=8, deadline_ms=2000.0,
                   mid_hook=None):
            """Open loop: submit at `rate`, collect concurrently.
            Returns (wall_s, errors, shed, n_late)."""
            out_q: "queue.Queue" = queue.Queue(maxsize=len(reqs) + 1)
            errors = [0]
            done = threading.Event()

            def collect():
                while True:
                    rr = out_q.get()
                    if rr is None:
                        return
                    try:
                        rr.result(30)
                    except Exception:  # noqa: BLE001 — counted
                        errors[0] += 1

            cts = [threading.Thread(target=collect, daemon=True,
                                    name=f"sfb-collect-{i}")
                   for i in range(collectors)]
            for c in cts:
                c.start()
            shed = 0
            late = 0
            start = time.perf_counter()
            for i, (b, ks, dn) in enumerate(reqs):
                target = start + i / rate
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                elif now - target > 0.05:
                    late += 1
                if mid_hook is not None and i == len(reqs) // 3:
                    mid_hook()
                try:
                    out_q.put(router.submit(ks, dense=dn,
                                            deadline_ms=deadline_ms))
                except Exception:  # noqa: BLE001 — shed at the router
                    shed += 1
                    errors[0] += 1
            submit_wall = time.perf_counter() - start
            for _ in cts:
                out_q.put(None)
            for c in cts:
                c.join()
            done.set()
            wall = time.perf_counter() - start
            return {"submit_wall_s": submit_wall, "wall_s": wall,
                    "errors": errors[0], "shed": shed, "late": late}

        out: dict = {"metric": METRIC, "unit": "qps"}
        try:
            # -- phase 0: one member, primed, same driver — the
            # same-box single-member open-loop reference ---------------
            fleet.add(1, warm=False)
            replay(gen_cover_requests(seed=2), rate=rate_qps,
                   deadline_ms=10000.0)

            # -- phase 1: steady (latency arm) + saturation (capacity
            # arm) open loops ------------------------------------------
            import gc

            def arm(n, rate):
                for m in fleet.members():
                    m.frontend.reset_stats()
                router.latency.reset()
                h0 = router.counters["hedges"]
                r0 = router.counters["reroutes"]
                routed0 = router.counters["routed"]
                gc.collect()
                gc.disable()
                try:
                    rep = replay(gen_requests(n, seed=3), rate=rate)
                finally:
                    gc.enable()
                lat = router.latency.percentiles()
                routed = router.counters["routed"] - routed0
                return {
                    "requests": n, "target_qps": round(rate, 1),
                    "achieved_qps": round(
                        (n - rep["errors"]) / rep["wall_s"], 1),
                    "request_ms": lat,
                    "errors": rep["errors"], "shed": rep["shed"],
                    "late_arrivals": rep["late"],
                    "hedges": router.counters["hedges"] - h0,
                    "reroutes": router.counters["reroutes"] - r0,
                    "hedge_rate": round(
                        (router.counters["hedges"] - h0)
                        / max(routed, 1), 4),
                    "per_member_batch": {
                        m.endpoint: m.frontend.stats().get("avg_batch", 0)
                        for m in fleet.members()},
                }

            single_arm = arm(max(n_steady // 2, 500), rate_qps)
            out["single_member_open_loop"] = single_arm

            # -- grow to the fleet: joiners warm-handoff from the
            # seasoned member, then a cover pass settles the CH
            # assignment's residual shares ----------------------------
            fleet.add(n_replicas - 1, warm=True)
            replay(gen_cover_requests(seed=2), rate=rate_qps,
                   deadline_ms=10000.0)

            steady = arm(n_steady, rate_qps)
            if os.environ.get("SFB_QUICK", "0") == "1":
                # tuning mode: steady arm only, skip the rest
                out["steady"] = steady
                out["value"] = steady["achieved_qps"]
                return out
            saturation = arm(n_steady, sat_qps)
            out["steady"] = steady
            out["saturation"] = saturation
            out["value"] = saturation["achieved_qps"]
            rst = router.stats()
            single_p99 = single_arm["request_ms"]["p99_ms"]
            out["vs_single_replica"] = {
                # committed-record prong: both arms clear the whole
                # committed single-replica record's throughput
                "committed_qps": base_qps, "committed_p99_ms": base_p99,
                "steady_qps_ratio": round(
                    steady["achieved_qps"] / base_qps, 3)
                if base_qps else None,
                "capacity_qps_ratio": round(
                    saturation["achieved_qps"] / base_qps, 3)
                if base_qps else None,
                # same-box p99 prong: fleet tail vs the one-member
                # same-driver arm at the same rate (arm 0) — the 2×
                # budget the acceptance names, measured without a host
                # generation change underneath it
                "single_open_loop_p99_ms": single_p99,
                "fleet_p99_over_single": round(
                    steady["request_ms"]["p99_ms"] / single_p99, 3)
                if single_p99 else None,
                # same-box closed-loop ceiling (SFB_SINGLE=1)
                "single_same_box_closed_loop": single_same_box,
                "capacity_vs_same_box": round(
                    saturation["achieved_qps"] / single_same_box["qps"],
                    3) if single_same_box else None,
            }

            # -- phase 2: kill-replica chaos ---------------------------
            victim = fleet.members()[-1]
            pre_n = fleet.size()
            rep2 = replay(gen_requests(n_chunk, seed=4), rate=rate_qps,
                          mid_hook=victim.crash)
            deadline = time.perf_counter() + 10
            while any(m.endpoint == victim.endpoint
                      for m in fleet.members(live_only=False)):
                if time.perf_counter() > deadline:
                    raise TimeoutError("crashed member never expired")
                time.sleep(0.05)
            rst2 = router.stats()
            out["chaos_kill"] = {
                "requests": n_chunk, "errors": rep2["errors"],
                "killed": victim.endpoint,
                "members_before": pre_n, "members_after": fleet.size(),
                "reroutes": rst2["reroutes"] - rst["reroutes"],
                "hedges": rst2["hedges"] - rst["hedges"],
            }

            # -- phase 3: warm rejoin + draining restart ---------------
            (warm_m,) = fleet.add(1, warm=True)
            handoff = fleet.events[-1].get("handoff")
            warm_curve = []
            miss0 = warm_m.lookup.tier.counters["misses"]
            for part in range(4):
                replay(gen_requests(n_chunk // 4, seed=10 + part),
                       rate=rate_qps)
                warm_curve.append(
                    int(warm_m.lookup.tier.counters["misses"] - miss0))
            oldest = fleet.members()[0]
            drain_clean = []

            def _drain_restart():
                drain_clean.append(fleet.drain(oldest.endpoint))
                fleet.add(1, warm=True)

            rep3 = replay(gen_requests(n_chunk, seed=5), rate=rate_qps,
                          mid_hook=_drain_restart)
            out["drain_restart"] = {
                "requests": n_chunk, "errors": rep3["errors"],
                "drained": oldest.endpoint,
                "drain_clean": bool(drain_clean and drain_clean[0]),
                "members": fleet.size(),
            }

            # -- phase 4: cold join (the comparison arm) ---------------
            (cold_m,) = fleet.add(1, warm=False)
            cold_curve = []
            miss0 = cold_m.lookup.tier.counters["misses"]
            for part in range(4):
                replay(gen_requests(n_chunk // 4, seed=20 + part),
                       rate=rate_qps)
                cold_curve.append(
                    int(cold_m.lookup.tier.counters["misses"] - miss0))
            out["join"] = {
                "warm": {"handoff": handoff, "miss_curve": warm_curve,
                         "misses": warm_curve[-1]},
                "cold": {"miss_curve": cold_curve,
                         "misses": cold_curve[-1]},
                "warm_lt_cold": warm_curve[-1] < cold_curve[-1],
            }

            # -- phase 5: canary → promote → rollback ------------------
            dg_v1 = crc32c(np.ascontiguousarray(flat_v1).tobytes())
            v1 = rollout.current
            v2 = rollout.begin_canary(flat_v2, fraction=0.2)
            canary_reqs = gen_requests(n_chunk, seed=6)
            expect = sum(router.in_canary_band(b, 0.2)
                         for b, _, _ in canary_reqs)
            rep5 = replay(canary_reqs, rate=rate_qps)
            counts = dict(router.stats()["version_counts"])
            rollout.promote()
            promoted = set(rollout.fleet_versions().values())
            rollout.rollback(reason="bench")
            back = rollout.fleet_versions()
            out["canary"] = {
                "errors": rep5["errors"],
                "version_counts": counts,
                "expected_canary": expect,
                "split_exact": counts.get(str(v2)) == expect,
                "promoted_all": promoted == {(v2, rollout.version_digest(
                    v2))},
                "rollback_versions": sorted(set(back.values())),
                "rollback_digest_ok": set(back.values()) ==
                {(v1, dg_v1)},
            }
            out["fleet_events"] = dict(fleet.counters)
            out["router"] = {k: v for k, v in router.stats().items()
                             if k not in ("members", "request")}
            out["population"] = n_keys
            out["replicas"] = n_replicas
            out["batch"] = max_batch
            out["coalesce_us"] = delay_us
            out["preload_s"] = round(preload_s, 2)
            out["platform"] = jax.devices()[0].platform
            out["host_cores"] = os.cpu_count()
            return out
        finally:
            fleet.stop()
            router.stop()


def main() -> None:
    try:
        rec = run()
    except Exception as e:  # noqa: BLE001 — one-JSON-line contract
        import traceback

        traceback.print_exc(file=sys.stderr)
        rec = {"metric": METRIC, "value": 0.0,
               "error": f"{type(e).__name__}: {e}"[:300]}
    print(json.dumps(rec))


if __name__ == "__main__":
    main()

#!/usr/bin/env bash
# CI gate (reference L0's cmake+ctest role): graftlint, native build,
# fast test gate, then the full matrix.
# Usage: ./ci.sh [lint [--changed]|sched|fast|full|chaos|ckpt|hot_tier|serving|serving_fleet|recsys|obs|slo|reshard|reconcile|endurance|tenancy]
#   sched — graftsched gate: deterministic-schedule exploration of the
#   control-plane protocol harnesses (tools/sched/models.py) — the
#   preemption-bound-2 schedule space EXHAUSTED plus seeded random
#   walks, every failure replayable from the printed seed, dynamic
#   lock-order observations cross-checked against the py_locks decls.
#   The JSON summary is archived like the lint one (SCHED_JSON).
#   chaos — PS high-availability fast-gate: every failover/replication
#   test with faultpoints armed (incl. the slow e2e kill-shard runs)
#   plus the chaos_ps demo with its recovery/overhead acceptance checks.
#   ckpt — crash-consistent job-checkpoint gate: the full
#   test_job_checkpoint.py matrix incl. the slow SIGKILL-the-job
#   mid-save e2e (restart + checksum-fallback + bit-identical resume),
#   plus the chaos_ckpt demo's save/restore/pause-window measurements.
#   hot_tier — persistent HBM hot-embedding-tier gate: RPC-only parity
#   (bit-identical through eviction churn + checkpoint/restore) and the
#   sparse_hot bench with its 0-RPC warm-steady-state assertion.
#   serving — online-serving-plane gate: the full serving suite (incl.
#   the chaos-gated kill-the-primary-mid-serve reattach/convergence
#   acceptance test) plus the serving bench with its zero-RPC-warm and
#   freshness thresholds asserted.
#   obs — unified observability plane gate: the obs suite (registry /
#   trace propagation / failover-replay span marking / aggregation)
#   plus the overhead bench asserting metrics-always-on ≤2% vs the
#   metrics-compiled-out baseline, the fixed 16-byte trace-context
#   header (tracing off adds ZERO bytes beyond it), and the ≥3-process
#   job snapshot with per-table wire bytes + observed density; the
#   trace demo re-generates the flow-linked cross-process timeline.
#   slo — continuous-telemetry gate: the time-series/SLO/flight-recorder
#   suites (incl. the slow kill-shard e2e), then the slo_demo run — a
#   delay-ms faultpoint armed mid-stream must make the watchdog fire the
#   step-time burn-rate alert, dump a postmortem bundle that parses and
#   contains the firing window, and the live exporter's /metrics must
#   validate as well-formed OpenMetrics; the overhead bench re-asserts
#   the sampler+watchdog cost inside the 2% budget.
#   endurance — cold-tier scale gate: the ssd cold-tier suite (admission
#   / compact index / block compression / io-budgeted bg compaction,
#   incl. the SIGKILL-mid-compaction chaos test), then the endurance
#   demo — a Zipf stream over a universe 50x the hot budget must admit
#   <=1/3 of offered uniques at the default threshold, measure <=16
#   index bytes per cold row, keep serve pull p99 bounded while the
#   background compactor churns, and checkpoint/restore digest-exact
#   mid-compaction (SSD_ENDURANCE.json is the archived artifact).
#   reshard — live elastic resharding + SLO-driven autoscaling gate:
#   the full reshard/autoscale suites incl. the slow chaos e2e (grow
#   2→4 and shrink back mid-CtrStreamTrainer with an armed kill-shard
#   during one migration — digests prove zero lost/doubled rows, final
#   state bit-identical to an unresharded oracle), then the closed-loop
#   diurnal-ramp demo: an injected traffic wave fires the step-time
#   SLO, the autoscaler grows the shard set live, the wave passes, the
#   alert clears and it shrinks back — RESHARD.json records the
#   cutover pause p50/p95 (asserted well under the full-copy bootstrap
#   time) and the scale-event journal.
#   reconcile — declarative-control-plane gate: the spec/reconciler/
#   simulator suite incl. the slow compound-transition chaos e2e
#   (canary open + grow 2→4 as ONE spec update with a kill-shard armed
#   mid-bootstrap, bit-identical to a sequential direct-primitive
#   oracle), then the game-day chaos schedule (tools/gameday.py —
#   every transition driven by writing desired state; GAMEDAY.json is
#   the committed artifact) and the policy simulator replaying both
#   committed traces at 1000-shard scale in well under a minute.
#   tenancy — multi-tenant isolation gate: the full tenancy suite
#   (wire-enforced namespaces, weighted admission, per-tenant quotas,
#   tenant-scoped control plane — incl. the slow abusive-neighbor
#   interference e2e), then the tenancy bench: a four-tenant workload
#   zoo (CTR streaming / routed-MoE / GNN sampling / TDM retrieval)
#   shares one cluster with a deliberately abusive tenant, and the
#   gate asserts the abuser's MARGINAL p99 damage stays bounded while
#   its meter shows throttles + quota refusals, every cross-tenant
#   probe bounces, and the neighbors' namespaces stay digest-identical
#   (TENANCY.json is the archived quiet-host artifact).
set -euo pipefail
cd "$(dirname "$0")"

# graftlint first, in every mode: a host-sync, lock-order or
# wire-contract violation fails in seconds, not after the pytest matrix
# (docs/STATIC_ANALYSIS.md). The JSON summary (per-pass wall time +
# finding counts, allowlist why-tags) is archived so a newly slow or
# noisy pass is visible in the log; run.py itself warns past the 10 s
# soft budget. `./ci.sh lint --changed` lints only files changed vs
# merge-base(HEAD, origin/main) — the sub-second pre-commit loop.
echo "== graftlint (10 passes: tracer/hot-path/locks-cc/locks-py/wire/conv/obs/loops/sync-shim/actuation) =="
LINT_JSON=${LINT_JSON:-/tmp/ci_lint_summary.json}
# --changed is a lint-mode-only knob: the full gates must always lint
# the whole tree (staleness + cross-module reachability need it)
if [[ "${1:-fast}" == "lint" && "${2:-}" == "--changed" ]]; then
  python tools/lint/run.py --json "$LINT_JSON" --changed
else
  python tools/lint/run.py --json "$LINT_JSON"
fi
python - "$LINT_JSON" <<'PYEOF'
import json, sys
s = json.load(open(sys.argv[1]))
per = s.get("per_pass", {})
slow = sorted(per.items(), key=lambda kv: -kv[1]["wall_ms"])[:3]
print("lint summary archived -> %s  (%.1fs total; slowest: %s)" % (
    sys.argv[1], s.get("wall_s", 0),
    ", ".join("%s %.0fms" % (k, v["wall_ms"]) for k, v in slow)))
PYEOF

if [[ "${1:-fast}" == "lint" ]]; then
  echo "CI OK (lint only)"
  exit 0
fi

echo "== native build =="
make -C paddle_tpu/csrc -s

if [[ "${1:-fast}" == "sched" ]]; then
  echo "== graftsched (schedule exploration: 3 protocol harnesses) =="
  # ~20k schedules in well under a minute on the CI host; the 240 s
  # budget is the wedge guard, not the expected cost. SCHED_SEED pins
  # the random-walk base seed for a bisection; every failure prints its
  # own standalone replay seed regardless.
  SCHED_JSON=${SCHED_JSON:-/tmp/ci_sched_summary.json}
  python tools/sched/run.py --json "$SCHED_JSON" --budget-s 240 \
    ${SCHED_SEED:+--seed "$SCHED_SEED"}
  python - "$SCHED_JSON" <<'PYEOF'
import json, sys
s = json.load(open(sys.argv[1]))
print("sched summary archived -> %s  (%d schedules, %.1fs)" % (
    sys.argv[1], s.get("total_schedules", 0),
    s.get("wall_ms", 0) / 1000.0))
PYEOF
  echo "CI OK (sched)"
  exit 0
fi

if [[ "${1:-fast}" == "endurance" ]]; then
  echo "== endurance gate: cold-tier admission/index/compression/io-budget =="
  # the suite first: a format or reconcile regression fails in seconds,
  # before the demo pays its stream (incl. the armed-SIGKILL chaos run)
  python -m pytest tests/test_ssd_cold_tier.py -q
  echo "== ssd endurance demo (Zipf stream, universe 50x hot budget) =="
  # the admission / index / digest asserts are exact; the p99 ratio and
  # RSS bounds carry shared-1-core-host headroom (the committed
  # SSD_ENDURANCE.json shows the quiet-host numbers: ~1.5x churn p99,
  # ~22 MB growth) — one retry absorbs ambient-load outliers
  check_endurance() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      SSD_END_OUT=${SSD_END_OUT:-/tmp/ci_ssd_endurance.json} \
      python tools/ssd_endurance_demo.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['universe'] >= 10 * d['hot_budget'], d
# THE admission acceptance: >=3x fewer rows than offered uniques at
# the default threshold (the singleton tail never earns a row)
assert d['offered_over_admitted'] >= 3.0, d
assert d['admit_rejects'] > 0, d
# THE index acceptance: <=16 measured bytes per cold row (44.7 baseline)
assert 0 < d['index_bytes_per_row'] <= 16.0, d
# io-budget isolation: serve p99 under compactor churn stays within a
# bounded multiple of the no-compaction baseline
assert d['pull_p99_ratio'] <= 10.0, d
assert d['bg_compactions'] > 0 and d['bg_backlog_final'] == 0, d
assert d['io_bg_bytes'] > 0, d
# durability: checkpoint taken mid-compaction restores digest-exact
assert d['digest_exact'] and d['digest_stable_under_churn'], d
assert d['restored_rows'] == d['saved_rows'] > 0, d
# RSS tracks the hot budget + index, never the universe
assert d['rss_growth_bytes'] <= 256 * 1024 * 1024, d
print('endurance OK: %.1fx admission leverage (%d uniques -> %d rows), '
      '%.1f index B/row, churn p99 %.2fx baseline (%.1fms), '
      'digest-exact restore of %d rows'
      % (d['offered_over_admitted'], d['offered_uniques'],
         d['admitted_rows'], d['index_bytes_per_row'],
         d['pull_p99_ratio'], d['pull_p99_ms_churn'],
         d['restored_rows']))"
  }
  check_endurance || { echo "endurance retry (ambient-load outlier)"; \
    check_endurance; }
  echo "CI OK (endurance)"
  exit 0
fi

if [[ "${1:-fast}" == "chaos" ]]; then
  echo "== chaos gate: PS HA failover/replication (faultpoints armed) =="
  # -m "" includes the slow e2e runs: kill-shard mid-CtrStreamTrainer
  # with sync-replication bit-identity, and the SIGKILL'd multiprocess
  # failover — the paths this gate exists to keep deterministic
  python -m pytest tests/test_ps_ha.py -q -m ""
  echo "== chaos_ps demo (recovery time + replication overhead) =="
  # the overhead measurement is an interleaved A/B on a shared host —
  # one retry absorbs ambient-load outliers (the A/A control measures
  # a ~10% noise floor on 2-core CI boxes; see tools/chaos_ps.py)
  check_chaos() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" CHAOS_TRIALS=3 CHAOS_AB_ROUNDS=6 \
      python tools/chaos_ps.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['recovery_ms_p95'] > 0 and d['recovery_trials'] >= 3, d
assert d['repl_overhead_pct'] <= 10.0, d
print('chaos_ps OK: recovery p50=%.0fms p95=%.0fms, repl overhead %.1f%%'
      % (d['recovery_ms_p50'], d['recovery_ms_p95'],
         d['repl_overhead_pct']))"
  }
  check_chaos || { echo "chaos_ps retry (ambient-load outlier)"; check_chaos; }
  echo "CI OK (chaos)"
  exit 0
fi

if [[ "${1:-fast}" == "ckpt" ]]; then
  echo "== ckpt gate: crash-consistent job checkpointing (SIGKILL e2e) =="
  # -m "" includes the slow acceptance run: SIGKILL the whole job
  # (trainers + PS) mid-save under an armed kill-job faultpoint,
  # restart, fall back past a deliberately-corrupted newest checkpoint
  # (checksum-detected), resume bit-identical to a fault-free oracle
  python -m pytest tests/test_job_checkpoint.py -q -m ""
  echo "== chaos_ckpt demo (save/restore latency + pause window) =="
  PYTHONPATH="$PWD:${PYTHONPATH:-}" CHAOS_CKPT_TRIALS=3 \
    CHAOS_CKPT_ROWS=20000 python tools/chaos_ckpt.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['fallback_ok'], d
assert d['save_ms_p95'] > 0 and d['restore_ms_p95'] > 0, d
assert 0 < d['pause_ms_p95'] < d['save_ms_p95'], d  # gate excludes bulk IO
print('chaos_ckpt OK: save p95=%.0fms restore p95=%.0fms pause p95=%.1fms'
      % (d['save_ms_p95'], d['restore_ms_p95'], d['pause_ms_p95']))"
  echo "CI OK (ckpt)"
  exit 0
fi

if [[ "${1:-fast}" == "hot_tier" ]]; then
  echo "== hot_tier gate: HBM tier ≡ RPC-only parity + 0-RPC warm steps =="
  # test_hot_tier.py carries the tier-level matrix (eviction churn,
  # every rule, checkpoint/restore, banked sharded mesh) — run before
  # the bench so a rule regression fails in seconds
  python -m pytest tests/test_hot_tier.py -q -m ""
  echo "== sparse_hot bench (single-chip + multi-host rung) =="
  PYTHONPATH="$PWD:${PYTHONPATH:-}" SHB_SAMPLES=2048 \
    python tools/sparse_hot_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
# THE acceptance counter: a warm steady-state step performs ZERO PS
# RPCs (RpcPsClient.op_counts delta over the measured epoch)
assert d['hot_tier']['rpc_per_step'] == 0.0, d['hot_tier']
assert d['hot_tier']['hit_rate'] == 1.0, d['hot_tier']
assert d['rpc_only']['rpc_per_step'] > 0, d['rpc_only']
# the multi-host rung (8 virtual CPU devices in a subprocess when the
# backend is single-device): warm sharded steps are 0-RPC too, and the
# hlo_bytes proof — the routed all_to_all id/vector exchange moves
# FEWER collective bytes than the gathered (all_gather+reduce_scatter)
# formulation. Byte counts come from the compiled HLO, so this assert
# is deterministic on a noisy box where timing is not.
s = d['sharded']; assert 'error' not in s, s
assert s['rpc_per_step'] == 0.0 and s['hit_rate'] == 1.0, s
assert s['shards'] == 8 and s['banks'] == 8, s
ex = s['exchange']
assert 0 < ex['alltoall']['exchange_bytes'] \
    < ex['gathered']['exchange_bytes'], ex
print('sparse_hot OK: %.0f samples/s single (%.2fx vs rpc-only), '
      '%.0f samples/s sharded, a2a exchange %.2fx of gathered bytes'
      % (d['value'], d['speedup_vs_rpc_only'], s['samples_per_sec'],
         ex['alltoall_over_gathered']))"
  echo "CI OK (hot_tier)"
  exit 0
fi

if [[ "${1:-fast}" == "serving" ]]; then
  echo "== serving gate: oplog-fed replicas + frontend (chaos incl.) =="
  # -m "" for symmetry with the other gates (the serving suite is all
  # fast today — the failover acceptance test included)
  python -m pytest tests/test_serving.py -q -m ""
  echo "== serving bench (warm p99 + push→servable freshness) =="
  # thresholds carry shared-2-core-host headroom (the committed
  # SERVING.json shows the quiet-host numbers: single-digit warm p99,
  # freshness p95 well under the 100 ms SLO); one retry absorbs
  # ambient-load outliers, the zero-RPC and zero-failure asserts are
  # exact on every attempt
  check_serving() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu SB_REQUESTS=1000 \
      python tools/serving_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['warm']['rpc_per_request'] == 0.0, d['warm']
assert d['warm']['shed'] == 0 and d['warm']['deadline_misses'] == 0, d['warm']
assert d['freshness_failures'] == 0, d['freshness']
assert d['warm']['request_ms']['p99_ms'] <= 50.0, d['warm']
assert d['freshness']['p95_ms'] <= 250.0, d['freshness']
print('serving OK: warm p99=%.1fms qps=%.0f, push→servable p95=%.1fms'
      % (d['warm']['request_ms']['p99_ms'], d['warm']['qps'],
         d['freshness']['p95_ms']))"
  }
  check_serving || { echo "serving retry (ambient-load outlier)"; check_serving; }
  echo "CI OK (serving)"
  exit 0
fi

if [[ "${1:-fast}" == "serving_fleet" ]]; then
  echo "== serving_fleet gate: router / fleet / rollout suite =="
  # -m "" for symmetry; the suite is all fast (stub-member router
  # semantics + real-replica fleet joins/drains/crash + the rollout
  # lifecycle incl. the primary-promotion re-attach heal)
  python -m pytest tests/test_serving_fleet.py -q -m ""
  echo "== fleet bench (open-loop replay + chaos + canary cycle) =="
  # gate the INVARIANTS exactly (zero errors through a kill-replica
  # round AND a draining restart, hedge rate bounded, warm-handoff
  # misses < cold-join misses, canary split exact + digest-pinned
  # rollback) and the throughput only loosely — absolute qps/p99 on a
  # shared 1-core box swing 2-3x with ambient load (the committed
  # SERVING_FLEET.json is the quiet-host run that also meets the
  # ≥baseline-qps / ≤2x-p99 acceptance); one retry absorbs outliers
  check_fleet() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      SFB_KEYS=8000 SFB_STEADY=2000 SFB_CHUNK=800 \
      python tools/serving_fleet_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['steady']['errors'] == 0, d['steady']
assert d['chaos_kill']['errors'] == 0, d['chaos_kill']
assert d['drain_restart']['errors'] == 0, d['drain_restart']
assert d['chaos_kill']['members_after'] == d['chaos_kill']['members_before'] - 1
assert d['steady']['hedge_rate'] <= 0.25, d['steady']
assert d['join']['warm']['misses'] < d['join']['cold']['misses'], d['join']
assert d['canary']['split_exact'], d['canary']
assert d['canary']['rollback_digest_ok'], d['canary']
assert d['steady']['achieved_qps'] >= 0.5 * d['steady']['target_qps'], d['steady']
print('serving_fleet OK: steady %.0f qps (p99 %.1f ms), capacity %.0f qps, '
      'kill+drain 0 errors, hedge %.1f%%, warm/cold misses %d/%d'
      % (d['steady']['achieved_qps'], d['steady']['request_ms']['p99_ms'],
         d['saturation']['achieved_qps'], 100 * d['steady']['hedge_rate'],
         d['join']['warm']['misses'], d['join']['cold']['misses']))"
  }
  check_fleet || { echo "serving_fleet retry (ambient-load outlier)"; check_fleet; }
  echo "CI OK (serving_fleet)"
  exit 0
fi

if [[ "${1:-fast}" == "recsys" ]]; then
  echo "== recsys gate: retrieval→ranking pipeline suite (incl. slow e2e) =="
  # -m "" deliberately includes the slow multi-process chaos e2e test
  python -m pytest tests/test_recsys_pipeline.py -q -m ""
  echo "== recsys replay (ramp + flash crowd + chaos + canary, multi-host members) =="
  # gate the INVARIANTS exactly (zero errors through the chaos kill and
  # the flash crowd, autoscaler journaled a grow, ranking actually
  # coalesced across requests, fleet-wide freshness bounded while the
  # trainer streams, canary/promote/rollback verified over the wire)
  # and latency only against the request deadline — absolute p99 on a
  # shared 1-core box swings with ambient load; one retry absorbs it.
  # The committed RECSYS_E2E.json is the quiet-host run of this exact
  # profile.
  check_recsys() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      RRB_KEYS=8000 RRB_MEMBERS=2 RRB_BASE_QPS=10 RRB_PEAK_QPS=40 \
      RRB_SPIKE_X=4 RRB_SLO_MS=60 RRB_DEADLINE_MS=8000 \
      RRB_RAMP_S=10 RRB_SPIKE_S=6 RRB_TAIL_S=6 RRB_SCALE_WAIT_S=45 \
      python tools/recsys_replay.py | tee /tmp/recsys_e2e_ci.json \
      | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['errors_total'] == 0, d['errors_total']
for ph in ('ramp', 'spike', 'tail'):
    assert d[ph]['within_deadline'], (ph, d[ph])
assert d['ramp']['members_before'] >= 2 and d['ramp']['killed'], d['ramp']
assert d['autoscale']['grew'], d['autoscale']
assert d['pipeline']['coalesce_factor'] > 1.0, d['pipeline']
assert d['spike']['coalesce_factor'] > 1.5, d['spike']
f = d['freshness_under_training']
assert f['failures'] == 0 and f['probes'] >= 5, f
assert f['p95_s'] is not None and f['p95_s'] <= 5.0, f
assert d['canary']['both_versions_served'], d['canary']
assert d['canary']['promoted_all'], d['canary']
assert d['canary']['rollback_digest_ok'], d['canary']
assert all(m['multi_host'] for m in d['members'].values()), d['members']
print('recsys OK: e2e %.0f qps, ramp/spike/tail p99 %.0f/%.0f/%.0f ms, '
      'coalesce %.2fx (spike %.2fx), freshness p95 %.2f s, '
      'grew=%s, 0 errors through chaos'
      % (d['value'], d['ramp']['e2e_ms']['p99_ms'],
         d['spike']['e2e_ms']['p99_ms'], d['tail']['e2e_ms']['p99_ms'],
         d['pipeline']['coalesce_factor'], d['spike']['coalesce_factor'],
         f['p95_s'], d['autoscale']['grew']))"
  }
  check_recsys || { echo "recsys retry (ambient-load outlier)"; check_recsys; }
  python -c "
import json
d = json.loads([l for l in open('/tmp/recsys_e2e_ci.json')
                if l.startswith('{')][-1])
open('RECSYS_E2E.json', 'w').write(json.dumps(d, indent=4) + '\n')
" 2>/dev/null || true
  echo "CI OK (recsys)"
  exit 0
fi

if [[ "${1:-fast}" == "slo" ]]; then
  echo "== slo gate: continuous telemetry / watchdog / flight recorder =="
  # -m "" includes the slow e2e: kill-shard mid-CtrStreamTrainer →
  # failover/breaker alerts + a postmortem bundle with the failing
  # request spans and the recovery visible in the metric timeline
  python -m pytest tests/test_slo.py tests/test_flightrec.py -q -m ""
  echo "== slo demo (injected degradation → alert → bundle → exporter) =="
  check_slo() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      SLO_OUT=/tmp/ci_obs_timeseries.json python tools/slo_demo.py \
      | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['alert']['rule'] == 'step_time_p95', d['alert']
assert d['alert_cleared'], d
assert d['bundle']['alert_in_degraded_window'], d['bundle']
assert d['bundle']['spans'] > 0, d['bundle']
assert d['bundle']['alert_instants_in_trace'] > 0, d['bundle']
assert d['openmetrics_ok'] and d['openmetrics_families'] > 5, d
assert d['timeline_alert_instants'] > 0, d
print('slo demo OK: alert @%.1fms threshold, bundle %s (%d spans), '
      '%d OpenMetrics families'
      % (d['threshold_ms'], d['bundle']['reason'], d['bundle']['spans'],
         d['openmetrics_families']))"
  }
  check_slo || { echo "slo demo retry (ambient-load outlier)"; check_slo; }
  echo "== obs overhead bench (sampler+watchdog inside the 2% budget) =="
  # same one-retry discipline as the obs gate: the min-over-passes
  # estimator still loses to whole-pass noisy-neighbor weather on this
  # VM (±30% swings observed at zero local load)
  check_slo_overhead() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      python tools/obs_overhead_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['value'] <= 2.0, d
assert d['sampler_ticks'] > 0 and d['watchdog_evaluations'] > 0, d
assert d['alerts_fired'] == 0, d  # healthy run: nothing may fire
print('slo overhead OK: %+.2f%% with %d sampler ticks, %d rule evals'
      % (d['value'], d['sampler_ticks'], d['watchdog_evaluations']))"
  }
  check_slo_overhead || { echo "slo overhead retry (ambient-load outlier)"; \
    check_slo_overhead; }
  echo "CI OK (slo)"
  exit 0
fi

if [[ "${1:-fast}" == "reshard" ]]; then
  echo "== reshard gate: live elastic resharding + SLO autoscaling =="
  # -m "" includes the slow chaos e2e: grow 2→4 + shrink 4→2 mid-
  # CtrStreamTrainer with a kill-shard during one migration, final
  # state bit-identical to an unresharded oracle
  python -m pytest tests/test_reshard.py tests/test_autoscale.py -q -m ""
  echo "== reshard demo (wave → SLO fire → grow → clear → shrink) =="
  check_reshard() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      RESHARD_OUT=/tmp/ci_reshard.json python tools/reshard_demo.py \
      | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['scaled_up']['to_shards'] == 4, d['scaled_up']
assert d['scaled_down']['to_shards'] == 2, d['scaled_down']
assert d['alert_cleared'] and d['shards_final'] == 2, d
# gate-hold must be a small fraction of the full-copy bootstrap —
# the reason snapshot+tail+fence beats stop-the-world
assert 0 < d['gate_hold_over_copy'] < 0.5, d
assert d['trainer_np_target'] == 2, d
print('reshard demo OK: wave fired %s, grow pause %.0fms vs copy '
      '%.0fms (ratio %.2f), shrink pause %.0fms, journal closed the '
      'loop'
      % (d['alert']['rule'], d['scaled_up']['cutover_pause_ms'],
         d['scaled_up']['bootstrap_s'] * 1e3, d['gate_hold_over_copy'],
         d['scaled_down']['cutover_pause_ms']))"
  }
  check_reshard || { echo "reshard demo retry (ambient-load outlier)"; \
    check_reshard; }
  echo "CI OK (reshard)"
  exit 0
fi

if [[ "${1:-fast}" == "reconcile" ]]; then
  echo "== reconcile gate: declarative control plane (spec/reconciler/simulator) =="
  # -m "" includes the slow compound-transition chaos e2e: canary open
  # + grow 2→4 proposed as ONE spec update, kill-shard mid-bootstrap,
  # digests/params bit-identical to a sequential direct-primitive oracle
  python -m pytest tests/test_reconcile.py -q -m ""
  echo "== game-day chaos schedule (spec-driven drill, armed faultpoints) =="
  # grow-under-fire / canary open+rollback via spec / shrink back —
  # every transition written as desired state, the journal must close
  # the loop on every step and the content digest must round-trip
  check_gameday() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      GAMEDAY_OUT=${GAMEDAY_OUT:-/tmp/ci_gameday.json} \
      python tools/gameday.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['digest_ok'] and d['traffic']['errors'] == 0, d
assert d['shards_final'] == 2, d
assert d['promotions'] >= 1, d   # the kill really fired mid-grow
steps = {s['step'] for s in d['schedule']}
assert steps == {'grow_under_fire', 'canary_open', 'canary_rollback',
                 'shrink'}, steps
assert all(s['converged'] for s in d['schedule']), d['schedule']
print('gameday OK: %d schedule steps converged, %d promotions under '
      'fire, digest round-tripped, %d pulls 0 errors (%.1fs)'
      % (len(d['schedule']), d['promotions'], d['traffic']['pulls'],
         d['wall_s']))"
  }
  check_gameday || { echo "gameday retry (ambient-load outlier)"; \
    check_gameday; }
  echo "== policy simulator (committed traces, 1000-shard scale) =="
  # the acceptance case: the stock policy rides RESHARD.json's diurnal
  # wave cleanly AND a hysteresis inversion is caught as oscillation —
  # both replays must finish inside the wall budget
  PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu python -c "
from paddle_tpu.ps.autoscale import AutoscaleConfig
from paddle_tpu.ps.simulate import (diurnal_wave_profile,
                                    flash_crowd_profile, simulate)
stock = simulate(AutoscaleConfig(min_shards=256, max_shards=1024),
                 diurnal_wave_profile('RESHARD.json', base_shards=512))
assert stock.wall_s < 60.0 and stock.max_shards_seen() == 1024, vars(stock)
assert stock.oscillations(15.0) == 0, stock.scale_events
broken = simulate(AutoscaleConfig(min_shards=256, max_shards=1024,
                                  cooldown_up_s=0.0, cooldown_down_s=0.0,
                                  clear_hold_s=0.0),
                  diurnal_wave_profile('RESHARD.json', base_shards=512),
                  fire_after_ticks=1, clear_after_ticks=1)
assert broken.oscillations(15.0) >= 5, broken.scale_events
flash = simulate(AutoscaleConfig(min_shards=256, max_shards=1024),
                 flash_crowd_profile('RECSYS_E2E.json', base_shards=256))
assert flash.wall_s < 60.0 and flash.oscillations(15.0) == 0, vars(flash)
print('simulator OK: diurnal %d ticks %.3fs wall (peak %d, 0 osc), '
      'inverted hysteresis caught (%d rapid reversals), flash crowd '
      'peak %d -> final %d'
      % (stock.ticks, stock.wall_s, stock.max_shards_seen(),
         broken.oscillations(15.0), flash.max_shards_seen(),
         flash.final_shards))"
  echo "CI OK (reconcile)"
  exit 0
fi

if [[ "${1:-fast}" == "obs" ]]; then
  echo "== obs gate: unified observability plane =="
  python -m pytest tests/test_obs.py -q -m ""
  echo "== obs overhead bench (metrics ≤2% on the DeepFM stream step) =="
  # interleaved A/B over ONE shared cluster, trimmed-mean of paired
  # per-round ratios, min over up to 3 passes (noisy-neighbor VM —
  # see the bench docstring); one retry covers the residual. The wire
  # asserts (fixed header, zero extra bytes with tracing off) and the
  # snapshot asserts (≥3 processes, wire bytes, density) are exact.
  check_obs() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      python tools/obs_overhead_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['value'] <= 2.0, d
assert d['wire_header_bytes'] == 28 + d['trace_ctx_bytes'], d
assert d['tracing_off_extra_header_bytes'] == 0, d
assert d['job_processes'] >= 3, d
assert any(v > 0 for v in d['server_wire_bytes'].values()), d
assert d['client_density'] and \
    all(0 < v <= 1.0 for v in d['client_density'].values()), d
print('obs overhead OK: %+.2f%% (on %.1fms / off %.1fms), header %dB '
      'fixed, %d-process snapshot'
      % (d['value'], d['step_ms_metrics_on'], d['step_ms_metrics_off'],
         d['wire_header_bytes'], d['job_processes']))"
  }
  check_obs || { echo "obs overhead retry (ambient-load outlier)"; check_obs; }
  echo "== obs trace demo (flow-linked cross-process timeline) =="
  PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
    OBS_TRACE_OUT=/tmp/ci_obs_trace.json python tools/obs_trace_demo.py \
    | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['flow_links'] > 0 and d['client_pull_spans'] > 0, d
assert d['server_pull_spans'] > 0 and d['job_processes'] >= 3, d
print('obs trace demo OK: %d flow links across %d events, %d processes'
      % (d['flow_links'], d['events'], d['job_processes']))"
  echo "CI OK (obs)"
  exit 0
fi

if [[ "${1:-fast}" == "tenancy" ]]; then
  echo "== tenancy gate: multi-tenant isolation suite (incl. slow interference e2e) =="
  # -m "" deliberately includes the slow abusive-neighbor e2e (four
  # well-behaved tenants + a flood that must throttle/quota-refuse
  # without moving a neighbor's p99 or writing one foreign row)
  python -m pytest tests/test_tenancy.py -q -m ""
  echo "== tenancy bench (workload zoo + abusive neighbor, marginal-p99 isolation) =="
  # the namespace/quota/digest asserts are exact on every attempt; the
  # p99 gate is the abuser's MARGINAL damage (abused vs shared — the
  # zoo running without the abuser), because solo→shared movement on a
  # shared 1-core box is CPU scheduling, not an isolation failure. The
  # 5x + 20 ms bound carries ambient-load headroom (the committed
  # TENANCY.json shows the quiet-host worst ratio: ~1.3x); one retry
  # absorbs the residual outliers.
  check_tenancy() {
    PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu \
      python tools/tenancy_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines()
                if l.startswith('{')][-1])
assert 'error' not in d, d
for n, t in d['tenants'].items():
    assert t['abused']['p99_ms'] <= 5.0 * t['shared']['p99_ms'] + 20.0, (n, t)
assert d['abuse']['flood']['throttled'] > 0, d['abuse']
assert d['abuse']['rows_within_cap'], d['abuse']
assert d['isolation']['cross_tenant_breaches'] == 0, d['isolation']
assert d['isolation']['cross_tenant_probes_bounced'] > 0, d['isolation']
assert d['isolation']['digest_stable_under_abuse'], d['isolation']
assert d['isolation']['wb_rows_unchanged'], d['isolation']
worst = max(d['tenants'].items(), key=lambda kv: kv[1]['p99_ratio'])
print('tenancy OK: worst marginal p99 %.2fx (%s), abuser throttled %d / '
      'quota-refused %d, %d cross-tenant probes bounced, 0 breaches'
      % (worst[1]['p99_ratio'], worst[0],
         d['abuse']['flood']['throttled'], d['abuse']['flood']['quota'],
         d['isolation']['cross_tenant_probes_bounced']))"
  }
  check_tenancy || { echo "tenancy retry (ambient-load outlier)"; check_tenancy; }
  echo "CI OK (tenancy)"
  exit 0
fi

echo "== hot-tier fast checks (parity / eviction churn / 0-RPC warm) =="
# the hot tier's bit-parity contract is the cheapest place to catch a
# sparse-rule or flush-back regression — fail it before the full matrix
python -m pytest tests/test_hot_tier.py -q

echo "== comm-fusion fast checks (fused dense-DP collectives + hlo_bytes) =="
# fail the fused-bucket/quantized-collective layer in seconds, before the
# full matrix — these cover the wire-byte acceptance gates directly
python -m pytest tests/test_comm_fusion.py tests/test_hlo_bytes.py -q

echo "== sparse-wire + placement fast checks (quantized push wire / swap) =="
# the ISSUE 14 loop: quantized push wire (EF parity, drain-at-quiesce,
# replicated-frame bit-identity, csrc dequant rejection) and the
# density-measured placement swap at a live reshard epoch fence —
# cheapest place to catch an encode/decode or swap-accounting regression
python -m pytest tests/test_sparse_wire.py tests/test_placement.py -q

echo "== fast gate (default: -m 'not slow') =="
# hot-tier/comm-fusion/hlo_bytes/sparse-wire already ran above — don't
# pay them twice
python -m pytest tests/ -q -x \
  --ignore=tests/test_comm_fusion.py --ignore=tests/test_hlo_bytes.py \
  --ignore=tests/test_hot_tier.py \
  --ignore=tests/test_sparse_wire.py --ignore=tests/test_placement.py

if [[ "${1:-fast}" == "full" ]]; then
  echo "== full matrix (slow tests included) =="
  python -m pytest tests/ -q -m ""
  echo "== driver artifacts =="
  python -c "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8); print('dryrun OK')"
  echo "== artifact tools smoke (tiny shapes, CPU) =="
  PYTHONPATH="$PWD:${PYTHONPATH:-}" SSD_DEMO_POP=200000 SSD_DEMO_PASS_KEYS=20000 \
    SSD_DEMO_PASSES=1 python tools/ssd_scale_demo.py | python -c \
    "import json,sys; d=json.load(sys.stdin); assert 'error' not in d, d; print('ssd_scale_demo OK')"
  PYTHONPATH="$PWD:${PYTHONPATH:-}" WD_POP=200000 WD_RECORDS=5000 WD_DAYS=1 \
    python tools/widedeep_daily.py | python -c \
    "import json,sys; d=json.load(sys.stdin); assert 'error' not in d, d; print('widedeep_daily OK')"
  PYTHONPATH="$PWD:${PYTHONPATH:-}" ANCHOR_POP=130000 ANCHOR_DAYS=1 \
    ANCHOR_STEPS_PER_DAY=20 ANCHOR_BATCH=256 ANCHOR_EVAL_EVERY=5 \
    ANCHOR_OUT=/tmp/ci_anchor_v2.json \
    python tools/make_anchor_v2.py | python -c \
    "import json,sys; d=json.loads(sys.stdin.read().splitlines()[-1]); \
assert d['gates']['parity_ok'], d; print('anchor_v2 parity OK')"
  # sparse push-wire ladder: the int8 wire must actually shrink the
  # SPARSE RPC push stream — ≥3× fewer bytes than fp32, asserted from
  # the PR 8 per-table byte counters (steady-state wire; the terminal
  # error-feedback drain is reported apart as a checkpoint-boundary
  # cost). Byte counts are exact — deterministic on a noisy box.
  PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu SWB_STEPS=8 \
    python tools/sparse_wire_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines() if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['value'] >= 3.0, d
by = {r['wire']: r for r in d['ladder']}
assert by['int8']['residual_rows_drained'] > 0, by  # EF really drained
assert by['fp16']['push_wire_bytes'] < by['fp32']['push_wire_bytes'], by
print('sparse wire ladder OK (int8 moves %.2fx fewer push bytes; '
      'fp16 %.2fx)' % (d['value'], d['ratio_fp32_over_fp16']))"
  # dense-DP comm ladder: int8 must actually shrink the wire (hlo_bytes-
  # measured ≥3.5× fewer collective bytes than fused fp32)
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    DCB_BATCH=256 DCB_STEPS=3 DCB_HIDDEN=128 \
    python tools/dense_comm_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines() if l.startswith('{')][-1])
assert 'error' not in d, d
ladder = {r['mode']: r for r in d['ladder']}
i8 = ladder['fused+int8']['collective_wire_bytes_per_step']
f32 = ladder['fused+fp32']['collective_wire_bytes_per_step']
assert f32 >= 3.5 * i8, ladder
print('dense comm ladder OK (int8 moves %.1fx fewer bytes)' % (f32 / i8))"
  # hot-embedding tier: a warm steady-state step must perform ZERO PS
  # RPCs (RpcPsClient.op_counts — the ISSUE 6 acceptance counter) and
  # the tier must not lose to the RPC-only path it replaces.
  # SHB_SHARDED=0: the dedicated hot_tier gate asserts the multi-host
  # rung (8-virtual-dev subprocess + exchange-byte proof) — the
  # embedded copy here would pay another PS cluster + mesh compile
  # unasserted
  PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu SHB_SAMPLES=2048 \
    SHB_SHARDED=0 python tools/sparse_hot_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines() if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['hot_tier']['rpc_per_step'] == 0.0, d['hot_tier']
assert d['hot_tier']['hit_rate'] == 1.0, d['hot_tier']
print('sparse_hot OK: 0 rpc/step warm, %.2fx vs rpc-only'
      % d['speedup_vs_rpc_only'])"
  # serving plane: warm requests perform ZERO RPCs and every freshness
  # probe lands (the dedicated `serving` gate asserts the latency
  # thresholds too — this full-gate copy pins the exact invariants at
  # a smaller scale)
  PYTHONPATH="$PWD:${PYTHONPATH:-}" JAX_PLATFORMS=cpu SB_KEYS=5000 \
    SB_REQUESTS=500 SB_PROBES=10 python tools/serving_bench.py | python -c "
import json, sys
d = json.loads([l for l in sys.stdin.read().splitlines() if l.startswith('{')][-1])
assert 'error' not in d, d
assert d['warm']['rpc_per_request'] == 0.0, d['warm']
assert d['freshness_failures'] == 0, d['freshness']
print('serving OK: warm p99=%.1fms, push→servable p95=%.1fms, 0 rpc warm'
      % (d['warm']['request_ms']['p99_ms'], d['freshness']['p95_ms']))"

  echo "== TSAN sweep (table/RPC/graph concurrency surfaces) =="
  # gate: OUR instrumented .so must stay report-free; third-party libs
  # (libjax_common Eigen/MLIR pools, libgcc unwind) are uninstrumented
  # and their shutdown-order mutex noise is filtered by the grep below,
  # not silently swallowed — the log files stay in /tmp for inspection.
  # The EXIT trap restores the normal flavor even when the sweep fails
  # (a leftover TSAN .so breaks every later non-preloaded import).
  # SANITIZE is EXPORTED, not passed on make's command line: the
  # package runs `make` itself when it loads the library
  # (ps/native.load_native), and make must see the same flavor there or
  # it would rebuild the plain library over the instrumented one.
  trap 'unset SANITIZE; make -C paddle_tpu/csrc -s' EXIT
  export SANITIZE=thread
  make -C paddle_tpu/csrc -s
  rm -f /tmp/ci_tsan_report*
  # exitcode=0: TSAN's default exit-66-if-anything-reported would mask
  # pytest's own status behind unavoidable third-party noise — the grep
  # below is the gate for OUR code, pytest's exit code for the tests
  # OPENBLAS_NUM_THREADS=1: numpy-2.x's OpenBLAS pool spawns at import
  # and deadlocks every LATER fork under the sanitizer preload (the
  # first lazy `np.testing` import runs an lscpu subprocess — the whole
  # sweep wedged there, 0% CPU). BLAS parallelism buys nothing under a
  # 10-20x sanitizer anyway.
  # shim pass-through smoke FIRST: under the sanitizer the sync shim
  # must hand back raw threading primitives (scheduler uninstalled) so
  # TSAN instruments the real locks — a shim that wrapped them in
  # Python objects would mask every native-level report below
  LD_PRELOAD="$(gcc -print-file-name=libtsan.so)" OPENBLAS_NUM_THREADS=1 \
    TSAN_OPTIONS="suppressions=$PWD/paddle_tpu/csrc/tsan.supp,halt_on_error=0,exitcode=0,log_path=/tmp/ci_tsan_report" \
    python -c "
import queue, threading
from paddle_tpu.core import sync as _sync
assert _sync.current_scheduler() is None
assert isinstance(_sync.Lock(), type(threading.Lock()))
assert isinstance(_sync.Condition(), threading.Condition)
assert isinstance(_sync.Queue(maxsize=2), queue.Queue)
t = _sync.Thread(target=lambda: None, name='shim-smoke'); t.start(); t.join()
print('sync shim pass-through OK (sanitizer sees raw primitives)')"
  LD_PRELOAD="$(gcc -print-file-name=libtsan.so)" OPENBLAS_NUM_THREADS=1 \
    TSAN_OPTIONS="suppressions=$PWD/paddle_tpu/csrc/tsan.supp,halt_on_error=0,exitcode=0,log_path=/tmp/ci_tsan_report" \
    python -m pytest tests/test_table_concurrency.py tests/test_ssd_table.py \
      tests/test_native_table.py tests/test_ps_rpc.py \
      tests/test_rpc_robustness.py tests/test_dist_graph.py \
      tests/test_rpc_parallel.py tests/test_ps_ha.py \
      tests/test_job_checkpoint.py tests/test_serving.py \
      tests/test_serving_fleet.py \
      tests/test_recsys_pipeline.py \
      tests/test_obs.py tests/test_slo.py tests/test_flightrec.py \
      tests/test_reshard.py tests/test_autoscale.py \
      tests/test_reconcile.py \
      tests/test_sparse_wire.py tests/test_tenancy.py -q -m ""
  if grep -l "libpaddle_tpu_native" /tmp/ci_tsan_report* 2>/dev/null; then
    echo "TSAN: reports implicate libpaddle_tpu_native.so (see /tmp/ci_tsan_report*)"
    exit 1
  fi
  echo "TSAN sweep OK (no reports in our .so)"

  echo "== ASAN sweep (same surfaces; heap/stack/use-after-free) =="
  # same contract as TSAN: detect_leaks=0 because the uninstrumented
  # Python/jax runtime "leaks" by design at interpreter exit; exitcode=0
  # so pytest's status gates the tests and the grep gates OUR .so
  export SANITIZE=address
  make -C paddle_tpu/csrc -s
  rm -f /tmp/ci_asan_report*
  LD_PRELOAD="$(gcc -print-file-name=libasan.so)" OPENBLAS_NUM_THREADS=1 \
    ASAN_OPTIONS="detect_leaks=0,halt_on_error=0,exitcode=0,log_path=/tmp/ci_asan_report" \
    python -c "
import queue, threading
from paddle_tpu.core import sync as _sync
assert _sync.current_scheduler() is None
assert isinstance(_sync.Lock(), type(threading.Lock()))
assert isinstance(_sync.Condition(), threading.Condition)
assert isinstance(_sync.Queue(maxsize=2), queue.Queue)
t = _sync.Thread(target=lambda: None, name='shim-smoke'); t.start(); t.join()
print('sync shim pass-through OK (sanitizer sees raw primitives)')"
  LD_PRELOAD="$(gcc -print-file-name=libasan.so)" OPENBLAS_NUM_THREADS=1 \
    ASAN_OPTIONS="detect_leaks=0,halt_on_error=0,exitcode=0,log_path=/tmp/ci_asan_report" \
    python -m pytest tests/test_table_concurrency.py tests/test_ssd_table.py \
      tests/test_native_table.py tests/test_ps_rpc.py \
      tests/test_rpc_robustness.py tests/test_dist_graph.py \
      tests/test_rpc_parallel.py tests/test_ps_ha.py \
      tests/test_job_checkpoint.py tests/test_serving.py \
      tests/test_serving_fleet.py \
      tests/test_recsys_pipeline.py \
      tests/test_obs.py tests/test_slo.py tests/test_flightrec.py \
      tests/test_reshard.py tests/test_autoscale.py \
      tests/test_reconcile.py \
      tests/test_sparse_wire.py tests/test_tenancy.py -q -m ""
  if grep -l "libpaddle_tpu_native" /tmp/ci_asan_report* 2>/dev/null; then
    echo "ASAN: reports implicate libpaddle_tpu_native.so (see /tmp/ci_asan_report*)"
    exit 1
  fi
  echo "ASAN sweep OK (no reports in our .so)"

  echo "== UBSAN sweep (same surfaces; UB: overflow/alignment/bounds) =="
  # UBSAN's runtime is linked into the sanitized .so itself, so no
  # LD_PRELOAD; halt_on_error=0 collects every report into the log
  export SANITIZE=undefined
  make -C paddle_tpu/csrc -s
  rm -f /tmp/ci_ubsan_report*
  OPENBLAS_NUM_THREADS=1 \
    UBSAN_OPTIONS="print_stacktrace=1,halt_on_error=0,log_path=/tmp/ci_ubsan_report" \
    python -c "
import queue, threading
from paddle_tpu.core import sync as _sync
assert _sync.current_scheduler() is None
assert isinstance(_sync.Lock(), type(threading.Lock()))
assert isinstance(_sync.Condition(), threading.Condition)
assert isinstance(_sync.Queue(maxsize=2), queue.Queue)
t = _sync.Thread(target=lambda: None, name='shim-smoke'); t.start(); t.join()
print('sync shim pass-through OK (sanitizer sees raw primitives)')"
  OPENBLAS_NUM_THREADS=1 \
    UBSAN_OPTIONS="print_stacktrace=1,halt_on_error=0,log_path=/tmp/ci_ubsan_report" \
    python -m pytest tests/test_table_concurrency.py tests/test_ssd_table.py \
      tests/test_native_table.py tests/test_ps_rpc.py \
      tests/test_rpc_robustness.py tests/test_dist_graph.py \
      tests/test_rpc_parallel.py tests/test_ps_ha.py \
      tests/test_job_checkpoint.py tests/test_serving.py \
      tests/test_serving_fleet.py \
      tests/test_recsys_pipeline.py \
      tests/test_obs.py tests/test_slo.py tests/test_flightrec.py \
      tests/test_reshard.py tests/test_autoscale.py \
      tests/test_reconcile.py \
      tests/test_sparse_wire.py tests/test_tenancy.py -q -m ""
  if grep -l "libpaddle_tpu_native" /tmp/ci_ubsan_report* 2>/dev/null; then
    echo "UBSAN: reports implicate libpaddle_tpu_native.so (see /tmp/ci_ubsan_report*)"
    exit 1
  fi
  echo "UBSAN sweep OK (no reports in our .so)"

  unset SANITIZE
  make -C paddle_tpu/csrc -s   # restore the normal flavor now
  trap - EXIT
fi
echo "CI OK"

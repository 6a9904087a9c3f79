"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the system under test from the cell's configuration and traffic
files (found by name, ``harness/spec.py``), warms up, measures whole
dispatches over their own elapsed time (``harness/window.py``), checks the
outputs against the plain reference on the device, and prints ONE JSON
object as the last line of stdout: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` when traced), then
``reference``, whose ``numbers`` hold every reading the reference was
compared by beside its limit (the last lines of stderr say the same).
Earlier lines carry the details: compile counts, every dispatch's
completion time, the losses, what the checks compared.

A run needs a TPU with at least the cell's chips and fails without one.
``--rehearse`` is the explicit exception: tiny sizes on the CPU (virtual
devices for a mesh cell) to exercise the harness end to end; its metrics
carry the prefix ``rehearsal.`` so that no CPU number appears under a
device metric's name.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import json
import math
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: seconds of a traced window: a trace is large and slows the host
TRACE_SECONDS = 4.0


def say(**kw) -> None:
    """One earlier line of stdout: a JSON object, stamped with the seconds
    since the process started."""
    kw["at_s"] = round(time.perf_counter() - _T_START, 3)
    print(json.dumps(kw), flush=True)


def compared(check, prefix: str = "") -> dict:
    """{name: [reading, limit]} of one check's result, flat: every reading
    that the ``tol`` beside it names (a ``tol`` may nest, a leaf a limit),
    and, for a comparison nested in it, whether that was ``ok`` beside 1.
    Copies what the ``checks`` line prints; computes nothing. A reading
    that is not finite goes in as its name ("nan", "inf"): the line stays
    JSON that any parser reads."""
    out = {}

    def pair(name, reading, limit):
        if isinstance(limit, dict):
            for k, v in limit.items():
                if isinstance(reading, dict) and k in reading:
                    pair(f"{name}.{k}", reading[k], v)
        else:
            reading = float(reading)
            out[name] = [reading if math.isfinite(reading) else repr(reading),
                         float(limit)]

    if prefix and "ok" in check:
        out[prefix + "ok"] = [float(check["ok"]), 1.0]
    for k, limit in check.get("tol", {}).items():
        if k in check:
            pair(prefix + k, check[k], limit)
    for k, v in check.items():
        if k != "tol" and isinstance(v, dict):
            out.update(compared(v, f"{prefix}{k}."))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on CPU; prints no device metric")
    args = ap.parse_args()

    sys.path.insert(0, HERE)
    sys.path.insert(0, ROOT)
    from harness import spec

    bench = spec.load_benchmark()
    cell = spec.Cell(bench, args.workload, rehearse=args.rehearse)
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")

    spans = {}
    t = time.perf_counter()
    import jax

    # the program's own placement of the persistent cache:
    # $JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache
    from paddle_tpu.core.compile_cache import enable_compile_cache

    if args.rehearse:
        cache_dir = None      # a rehearsal leaves nothing in the chip's cache
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        cache_dir = enable_compile_cache()
        # every program goes to the cache, not only those over 1 s: a later
        # run of the cell then compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from harness import device, trace, window
    from harness.compile_log import CompileLog

    spans["import_s"] = time.perf_counter() - t
    t = time.perf_counter()
    devices = jax.devices()
    spans["devices_s"] = time.perf_counter() - t
    plat = devices[0].platform
    if not args.rehearse and plat != "tpu":
        print(f"benchmark needs a TPU: jax found platform={plat!r} "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"cell {cell.name} needs {cell.chips} chips, jax found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    if not args.rehearse:
        device.peaks(devices[0].device_kind)    # unknown kind: an error
    log = CompileLog()
    say(run={"workload": cell.name, "seed": args.seed, "seconds": seconds,
             "trace": args.trace, "rehearse": args.rehearse,
             "platform": plat, "device_kind": devices[0].device_kind,
             "devices": len(devices), "compile_cache": cache_dir,
             "jax": jax.__version__})

    t = time.perf_counter()
    system = cell.adapter().build(cell, args.seed, devices, args.rehearse,
                                  cell.generator(), spans)
    spans["build_s"] = time.perf_counter() - t

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_out", "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        seconds = min(seconds, TRACE_SECONDS)
    t = time.perf_counter()
    win = window.measure(system, seconds, log, trace_dir, devices)
    spans["warmup_s"] = win["t0"] - t
    facts = device.device_facts(devices, win["hbm_window_bytes"])
    say(memory_stats=devices[0].memory_stats(),
        hbm_window_bytes=win["hbm_window_bytes"])
    setup_s = win["t0"] - _T_START
    handles = win.pop("handles")
    K = len(devices)
    rate = win["dispatches"] * system.units_per_dispatch / win["elapsed_s"] / K
    attempted = win["dispatches"] * system.steps_per_dispatch
    failed, losses = system.outcomes(handles)
    say(compile={"requests": log.requests, "cache_hits": log.hits,
                 "cache_written": log.written,
                 "in_window": win["compiles_in_window"]},
        spans={k: round(v, 4) for k, v in spans.items()})
    say(window={k: win[k] for k in ("elapsed_s", "dispatches",
                                    "warmup_dispatches")},
        warmup_done_s=[round(x, 6) for x in win["warmup_done_s"]],
        dispatch_done_s=[round(x, 6) for x in win["done_s"]],
        loss_first_last=[losses[0], losses[-1]])

    checks = {}
    for name, check in (
            ("state", system.check_state),
            ("reference", lambda: system.check_reference(cell.reference())),
            ("finish", lambda: system.finish(flush=bool(args.trace)))):
        t = time.perf_counter()
        checks[name] = check()
        checks[name]["took_s"] = round(time.perf_counter() - t, 3)
    checks["no_compile_in_window"] = {"ok": win["compiles_in_window"] == 0}
    say(checks=checks, compile_requests=log.requests, cache_hits=log.hits)
    correct = all(c["ok"] for c in checks.values()) and failed == 0

    ctx = {"cell": cell, "system": system, "window": win, "spans": spans,
           "rate_per_chip": rate, "chips": K, "setup_s": setup_s,
           "hbm_window_bytes": win["hbm_window_bytes"],
           "device_kind": devices[0].device_kind, "rehearse": args.rehearse,
           "trace": None, "hlo_text": ""}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    numbers = compared(checks["reference"])
    # last in the line: what a refusal's record keeps is the line's end
    reference = {"config": cell.config_name,
                 "file": f"{os.path.relpath(HERE, ROOT)}/configs/"
                         f"{cell.config['reference']}.py",
                 "agrees": bool(checks["reference"]["ok"]),
                 "numbers": numbers}
    if args.trace:
        events, layout = trace.load_events(trace.find_xplane(trace_dir))
        say(trace_layout=layout)
        red = trace.reduce_trace(events)
        if not red["devices"] and not args.rehearse:
            print("the trace holds no device operation", file=sys.stderr)
            return 4
        ctx["hlo_text"] = system.compiled_text()
        if red["devices"]:
            ctx["trace"] = red
            facts["busy_s"] = red["busy_s"]
            facts["window_s"] = red["window_s"]
            top = sorted(red["op_self_s"].items(), key=lambda kv: -kv[1])[:10]
            out["breakdown"] = {
                "device_ops": [[trace.op_label(n), s] for n, s in top],
                "idle_gaps": red["idle_gaps"][:10]}
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            name = ("rehearsal." if args.rehearse else "") + m["name"]
            metrics[name] = {"value": float(value), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = facts
    if args.rehearse:
        out["rehearsal"] = True
    out["reference"] = reference
    print(json.dumps(out), flush=True)
    # the driver's record of a run that is not correct keeps the END of
    # both streams: the same pairs are the last lines of stderr
    for name, (reading, limit) in numbers.items():
        print(f"{name} {reading!r} limit {limit!r}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

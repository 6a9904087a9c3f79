"""A cell's set-up by phase and its window's rate, without the check.

    chiprun --chips 1 -- python3 tools/setup_phases.py --workload <cell> --seed <n>

``benchmarks/run.py``'s own phases with its own code — the imports, the
devices, the adapter's ``build`` (the router balance inside it), the
warm-up and the window of ``harness/window.measure`` — and then it stops:
no reference check (150 of an LFM2 run's 190 s, 250 of JoyAI's 300), no
trace, no metric files. For telling the program's part of ``setup_s``
(``setup_s - devices_s``) from the machine's (``devices_s``: the TPU's
start-up, 8-16 s, PERF.md section 2) over many alternating runs of two
trees; ``correct`` is ``run.py``'s to decide. ``--tree DIR`` runs another
checkout's benchmark and program (a ``git archive`` of the parent), each
tree in a process of its own. Prints one JSON line: ``spans`` as ``run.py``
prints them, ``setup_s``, ``program_setup_s``, the compile counts with
``cache_written``, the window's rate a chip, the step's
``dispatch_rows_walked`` / ``dispatch_rung`` where the program has the
buffer, and the size of each cache entry the run wrote; and, where the
tree's program records ``pt.compile*`` spans (``core/profiler``, PR 38),
``setup_spans``: the five sums the ``setup_*`` metrics read, before it a
line with the costliest functions of each kind and the ring's fill
(``harness/setup_spans.py``). On a TPU only;
``--rehearse`` drives the same path at ``run.py``'s rehearsal sizes on the
CPU (its times are no device numbers).
"""
import time

_T_START = time.perf_counter()

import argparse
import json
import os
import sys


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args()
    root = os.path.abspath(args.tree)
    sys.path.insert(0, os.path.join(root, "benchmarks"))
    sys.path.insert(0, root)
    os.chdir(root)
    from harness import spec

    bench = spec.load_benchmark()
    cell = spec.Cell(bench, args.workload, rehearse=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    spans = {}
    t = time.perf_counter()
    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from harness import window
    from harness.compile_log import CompileLog

    spans["import_s"] = time.perf_counter() - t
    t = time.perf_counter()
    devices = jax.devices()
    spans["devices_s"] = time.perf_counter() - t
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"needs a TPU, found {devices[0].platform}", file=sys.stderr)
        return 3
    devices = devices[:cell.chips]
    before = set(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else set()
    log = CompileLog()
    t = time.perf_counter()
    system = cell.adapter().build(cell, args.seed, devices, args.rehearse,
                                  cell.generator(), spans)
    spans["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    win = window.measure(system, seconds, log, None, devices)
    spans["warmup_s"] = win["t0"] - t
    setup_s = win["t0"] - _T_START
    rate = win["dispatches"] * system.units_per_dispatch / win["elapsed_s"] \
        / len(devices)
    out = {"workload": cell.name, "seed": args.seed, "tree": root,
           "spans": {k: round(v, 4) for k, v in spans.items()},
           "setup_s": round(setup_s, 4),
           "program_setup_s": round(setup_s - spans["devices_s"], 4),
           "compile": {"requests": log.requests, "cache_hits": log.hits,
                       "cache_written": log.written,
                       "in_window": win["compiles_in_window"]},
           "rate_per_chip": rate, "unit": system.unit,
           "dispatches": win["dispatches"],
           "hbm_window_gib": win["hbm_window_bytes"] / 2 ** 30}
    buffers = getattr(getattr(system, "trainer", None), "state",
                      {}).get("buffers", {})
    if "dispatch_rows_walked" in buffers:
        out["rows_walked"] = buffers["dispatch_rows_walked"].tolist()
        out["rung"] = buffers["dispatch_rung"].tolist()
    try:        # a tree older than the spans has no reader for them
        from harness import setup_spans as ss
    except ImportError:
        ss = None
    ctx = {"window": win}
    if ss is not None and ss.before_t0(ctx) is not None:
        out["setup_spans"] = {
            "trace_s": ss.seconds(ctx, ss.TRACE),
            "lower_s": ss.seconds(ctx, ss.LOWER),
            "compile_s": ss.seconds(ctx, ss.COMPILE, hit=0),
            "cache_read_s": ss.seconds(ctx, ss.COMPILE, hit=1),
            "compile_misses": ss.count(ctx, ss.COMPILE, hit=0)}
    if os.path.isdir(cache_dir):
        out["cache_entries_written_mib"] = sorted(
            round(os.path.getsize(os.path.join(cache_dir, n)) / 2 ** 20, 2)
            for n in set(os.listdir(cache_dir)) - before)[::-1][:6]
    system.finish(flush=False)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

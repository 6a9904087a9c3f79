"""One whole pass of a CTR cell under the device profiler: where the time
of ``begin_pass`` and ``end_pass`` goes, on the device trace's clock.

    python3 tools/pass_profile.py [--workload deepfm_pass_zipf] [--seed N]
                                  [--dispatches 3] [--out chiprun_out/pass_profile.json]

Builds the cell's system through the benchmark's adapter (so sizes, data
and step are the cell's), with ``jax.profiler`` running from before
``begin_pass`` to after ``end_pass``: ``begin_pass`` -> ``--dispatches``
dispatches -> ``end_pass``. The program's ``pt.pass.*`` spans
(``core/profiler.RecordEvent`` -> ``TraceAnnotation``) then sit in the same
``.xplane.pb`` as the device's lines. For every span this prints its
start and duration as the trace has them, the duration the program's own
ring recorded (``profiler.host_spans()``), and how long each device line
(``XLA Ops``, and whatever other lines the device planes carry: transfers,
modules) was busy inside it. A builder's tool, not a metric: needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="deepfm_pass_zipf")
    ap.add_argument("--seed", type=int, default=24)
    ap.add_argument("--dispatches", type=int, default=3)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "pass_profile.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU: exercises this script only")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    from harness import spec, trace
    from paddle_tpu.core import profiler
    from paddle_tpu.core.compile_cache import enable_compile_cache

    devices = jax.devices()
    if not args.rehearse:
        enable_compile_cache()
        if devices[0].platform != "tpu":
            print(f"needs a TPU, found {devices[0].platform}",
                  file=sys.stderr)
            return 3
    cell = spec.Cell(spec.load_benchmark(), args.workload,
                     rehearse=args.rehearse)
    devices = devices[:cell.chips]
    trace_dir = os.path.join(ROOT, ".bench_out", "pass_profile")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    spans = {}
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        system = cell.adapter().build(cell, args.seed, devices,
                                      args.rehearse, cell.generator(), spans)
        feeder = system.feeder()
        handles = [system.dispatch(next(feeder))
                   for _ in range(args.dispatches)]
        jax.block_until_ready(handles)
        feeder.close()
        t_steps = time.perf_counter()
        system.check_state()            # finish() compares against its sample
        done = system.finish(flush=True)
    finally:
        jax.profiler.stop_trace()
    wall = time.perf_counter() - t0

    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace.find_xplane(trace_dir))
    host, device_lines = [], {}
    for plane in data.planes:
        for line in plane.lines:
            evs = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                   for e in line.events]
            if plane.name.startswith("/host:"):
                host += [e for e in evs if e[0].startswith("pt.pass.")
                         or e[0].startswith("pserver_")]
            elif evs and line.name != "Steps" and (
                    "TPU" in plane.name or "Chip" in plane.name):
                # ("Steps" spans from one program's launch to the next:
                # a clock, not work)
                device_lines[f"{plane.name} | {line.name}"] = trace._union(
                    (s, s + d) for _, s, d in evs)
    # one cache in this process, so one span of each name
    ring = {s.name: s for s in profiler.host_spans()}
    host.sort(key=lambda e: e[1])
    origin = host[0][1] if host else 0.0
    rows = []
    for name, start, dur in host:
        if not name.startswith("pt.pass."):
            continue
        rec = ring.get(name)
        busy = {k: dur - trace._subtract((start, start + dur), cover)
                for k, cover in device_lines.items()}
        rows.append({"span": name, "start_s": start - origin, "dur_s": dur,
                     "ring_dur_s": rec.dur if rec else None,
                     "counts": rec.counts if rec else None,
                     "device_busy_s": {k: v for k, v in busy.items()
                                       if v > 1e-9}})
    out = {"workload": args.workload, "seed": args.seed,
           "device_kind": devices[0].device_kind, "chips": len(devices),
           "wall_s": wall, "steps_done_at_s": t_steps - t0,
           "adapter_spans": spans, "flush_check": done, "spans": rows,
           "device_lines": {k: {"busy_s": trace._total(cover)}
                            for k, cover in device_lines.items()}}
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    for r in rows:
        busy = ", ".join(f"{k.split('| ')[-1]} {v:.3f}"
                         for k, v in r["device_busy_s"].items())
        print(f"{r['span']:22s} at {r['start_s']:8.3f} s  {r['dur_s']:8.3f} s"
              f"  ring {r['ring_dur_s'] or 0:8.3f}  {r['counts']}  "
              f"device: {busy or 'idle'}")
    print(json.dumps({k: out[k] for k in ("wall_s", "adapter_spans",
                                          "device_lines", "flush_check")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Check the yardstick itself, on the CPU, in about two minutes.

    python3 benchmarks/selfcheck.py

1. Every name, unit and line of ``BENCHMARK.json`` against the allowed
   characters and lengths, and every file a cell names is there.
2. The trace reduction on the small recorded trace
   (``testdata/small_trace.json``): busy, window, idle share, time per
   operation with a ``while`` container, one exposed and one overlapped
   collective, the idle gap's host span — answers known by hand.
3. FLOPs per token of ERNIE 1.0 base at 512 against a hand count, and the
   HLO readers on a two-instruction module.
4. The window's estimator on a fake system with a known dispatch time.
5. Every cell end to end at a tiny size through ``run.py --rehearse``
   (4 virtual devices for the mesh cell), traced and untraced: exit 0,
   ``correct``, no metric printed under a device metric's name, and the
   line ends in ``reference.numbers``: each reading beside its limit.
6. ``run.py`` without ``--rehearse`` off-TPU: non-zero exit, no result.
7. ``pytest benchmarks/tests`` (beside the rehearsals): what ``correct``
   excuses at a router's near-tie and the planted faults it refuses.

No number this prints is a device number.
"""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import flops, hlo, spec, trace, window  # noqa: E402

FAULTS = []


def check(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        FAULTS.append(what)


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-15)


def contract(bench) -> None:
    bad = spec.check_contract(bench)
    check(not bad, f"BENCHMARK.json: names, units, lengths {bad or ''}")
    for w in bench["workloads"]:
        cell = spec.Cell(bench, w["name"])
        for what, load, entry in (("adapter", cell.adapter, "build"),
                                  ("reference", cell.reference, "compare"),
                                  ("generator", cell.generator, "generate")):
            check(callable(getattr(load(), entry)),
                  f"{w['name']}: {what} file found by name")
        for m in cell.end_to_end + cell.per_layer:
            check(callable(spec.load_module("metrics", m["name"]).read),
                  f"{w['name']}: reader metrics/{m['name']}.py")
        spec.Cell(bench, w["name"], rehearse=True)   # rehearsal sizes merge


def trace_reduction() -> None:
    with open(os.path.join(HERE, "testdata", "small_trace.json")) as f:
        rec = json.load(f)
    got, want = trace.reduce_trace(rec["events"]), rec["expect"]
    for k in ("devices", "busy_s", "window_s", "idle_share", "collective_s",
              "collective_exposed_s"):
        check(close(got[k], want[k], 1e-9), f"trace: {k} = {want[k]}")
    check(set(got["op_self_s"]) == set(want["op_self_s"]) and all(
        close(got["op_self_s"][k], v) for k, v in want["op_self_s"].items()),
        "trace: time per operation, container and async span left out")
    check(got["idle_gaps"][0][0] == want["first_gap"][0]
          and close(got["idle_gaps"][0][1], want["first_gap"][1]),
          "trace: the idle gap is the host's sync")
    name = ("%fusion.18 = f32[33554433,12]{0,1:T(8,128)} fusion(f32[106496,12]"
            "{0,1} %a, s32[106496]{0} %b), kind=kCustom, calls=%fc")
    check(trace.op_name(name) == "fusion.18"
          and trace.op_label(name) == "fusion.18 f32[33554433,12]"
          and hlo.instruction_dims(name) == {"fusion.18": 33554433},
          "trace: an operation's name, label and leading dimension")


def counts() -> None:
    cfg = {"hidden_size": 768, "intermediate_size": 3072,
           "num_hidden_layers": 12, "vocab_size": 18000}
    # by hand: per layer 2*768*2304 + 4*512*768 + 2*768*768 + 4*768*3072
    # = 3538944 + 1572864 + 1179648 + 9437184 = 15728640; x12 = 188743680;
    # head 2*768*18000 = 27648000; forward 216391680; x3
    check(flops.encoder_train_flops_per_token(cfg, 512) == 649175040.0,
          "flops: ERNIE 1.0 base at 512 = 649,175,040 per token")
    text = """ENTRY %main (p: f32[8,4]) -> f32[8,4] {
  %all-to-all.2 = f32[4,100,9]{2,1,0} all-to-all(f32[4,100,9]{2,1,0} %x), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
}"""
    # 4*100*9 f32 = 14400 B operand, 3/4 of it leaves the chip
    check(hlo.report(text)["wire_bytes_total"] == 10800.0,
          "hlo: all-to-all wire bytes")


class _FakeSystem:
    """Dispatch = a timer thread that finishes 20 ms after the previous."""

    def __init__(self) -> None:
        self.free_at = time.perf_counter()

    def feeder(self):
        class F:
            def __iter__(self):
                return self

            def __next__(self):
                return 0

            def close(self):
                pass

        return F()

    def dispatch(self, item):
        self.free_at = max(self.free_at, time.perf_counter()) + 0.02
        return _Pending(self.free_at)


class _Pending:
    def __init__(self, ready_at: float) -> None:
        self.ready_at = ready_at

    def block_until_ready(self):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        return self


def estimator() -> None:
    class Log:
        requests = 0

    win = window.measure(_FakeSystem(), 0.3, Log(), None, [])
    per = win["elapsed_s"] / win["dispatches"]
    check(abs(per - 0.02) < 0.002 and win["elapsed_s"] >= 0.3
          and win["dispatches"] == len(win["done_s"])
          and win["warmup_dispatches"] >= window.MIN_WARM,
          f"window: whole dispatches over their own time "
          f"({win['dispatches']} in {win['elapsed_s']:.3f} s)")


def rehearsals(bench) -> None:
    run = [sys.executable, os.path.join(HERE, "run.py")]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    jobs = []
    for w in bench["workloads"]:
        for traced in (0, 1):
            jobs.append((w["name"], traced, subprocess.Popen(
                run + ["--workload", w["name"], "--seed", "1", "--seconds",
                       "1", "--trace", str(traced), "--rehearse"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
                text=True)))
    cpu = dict(env, JAX_PLATFORMS="cpu")
    off = subprocess.Popen(run + ["--workload", bench["workloads"][0]["name"],
                                  "--seed", "1", "--seconds", "1"],
                           stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                           env=cpu, text=True)
    tests = subprocess.Popen(
        [sys.executable, "-m", "pytest", os.path.join(HERE, "tests"), "-q",
         "-p", "no:cacheprovider"], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, env=cpu, text=True)
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for name, traced, proc in jobs:
        out, _ = proc.communicate(timeout=300)
        last = out.strip().splitlines()[-1] if out.strip() else "{}"
        try:
            res = json.loads(last)
        except ValueError:
            res = {}
        ok = (proc.returncode == 0 and res.get("correct") is True
              and res.get("rehearsal") is True and res.get("failed") == 0
              and res.get("attempted", 0) > 0
              and res.get("metrics")
              and all(k.startswith("rehearsal.") and k[10:] in names
                      for k in res["metrics"]))
        check(bool(ok), f"rehearsal: {name} --trace {traced} "
              f"({sorted(res.get('metrics', {}))})")
        # what a refusal's record keeps: every reading beside its limit,
        # under the key that ends the line
        ref = res.get("reference", {})
        numbers = ref.get("numbers", {})
        check(list(res)[-1:] == ["reference"] and ref.get("agrees") is True
              and list(ref)[-1:] == ["numbers"] and len(numbers) >= 2
              and all(len(v) == 2 and all(isinstance(x, float) for x in v)
                      for v in numbers.values()),
              f"rehearsal: {name} --trace {traced} ends in the "
              f"{len(numbers)} numbers its reference was compared by")
    out, _ = off.communicate(timeout=300)
    check(off.returncode != 0 and '"correct"' not in out,
          "run.py off-TPU without --rehearse: fails, prints no result")
    out, _ = tests.communicate(timeout=900)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    check(tests.returncode == 0 and " passed" in last,
          f"pytest benchmarks/tests: {last.strip('= ')}")
    if tests.returncode:
        print(out[-4000:], flush=True)


def main() -> int:
    t = time.perf_counter()
    bench = spec.load_benchmark()
    contract(bench)
    trace_reduction()
    counts()
    estimator()
    rehearsals(bench)
    print(f"{len(FAULTS)} fault(s) in {time.perf_counter() - t:.0f} s")
    return 1 if FAULTS else 0


if __name__ == "__main__":
    sys.exit(main())

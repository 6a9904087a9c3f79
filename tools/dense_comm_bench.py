"""Dense-DP comm micro-bench: the compression degradation ladder.

Measures one MLP train step on a pure-dp mesh over every rung of the
comm ladder — fused+int8 → fused+bf16 → fused fp32 → unfused per-tensor
baseline — emitting step time AND the compiled program's collective
bytes/step (tools/hlo_bytes.py, post-optimization HLO: what this
backend actually puts on the wire; note XLA CPU float-normalization
legalizes bf16 collectives to f32, so the bf16 rung only narrows on
TPU-class backends — the int8 rung narrows everywhere).

The headline ``value`` is the step time of the FIRST rung that builds
and runs (the degradation-ladder contract: a novel compile failure in a
quantized path costs a rung, not the number); every rung's result (or
error) is recorded under ``ladder``.

Standalone: prints exactly ONE JSON line (driver contract). Importable:
``run()`` returns the record. Env knobs: DCB_BATCH, DCB_STEPS,
DCB_WARMUP, DCB_HIDDEN, DCB_LAYERS, DCB_BUCKET_MB, DCB_BLOCK.
"""

import json
import os
import sys
import time

METRIC = "dense_dp_comm_step_ms"


def run() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import hlo_bytes

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.comm_fusion import CommFusionConfig
    from paddle_tpu.parallel import SpmdTrainer
    from jax.sharding import Mesh

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        return {"metric": METRIC, "value": 0.0,
                "error": f"need >=2 devices for a dp mesh, have {n}"}
    mesh = Mesh(np.array(devs), ("dp",))

    batch = int(os.environ.get("DCB_BATCH", 1024))
    steps = int(os.environ.get("DCB_STEPS", 15))
    warmup = max(1, int(os.environ.get("DCB_WARMUP", 3)))
    hidden = int(os.environ.get("DCB_HIDDEN", 256))
    layers = int(os.environ.get("DCB_LAYERS", 3))
    bucket_mb = float(os.environ.get("DCB_BUCKET_MB", 4.0))
    block = int(os.environ.get("DCB_BLOCK", 256))

    def fresh():
        pt.seed(0)
        mods = [nn.Linear(32, hidden), nn.ReLU()]
        for _ in range(layers - 1):
            mods += [nn.Linear(hidden, hidden), nn.ReLU()]
        mods += [nn.Linear(hidden, 8)]
        return nn.Sequential(*mods)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 32)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 8, batch).astype(np.int32))

    rungs = [
        ("fused+int8", CommFusionConfig(bucket_mb=bucket_mb, quant="int8",
                                        block_size=block)),
        ("fused+bf16", CommFusionConfig(bucket_mb=bucket_mb, quant="bf16")),
        ("fused+fp32", CommFusionConfig(bucket_mb=bucket_mb)),
        ("unfused", CommFusionConfig(fuse=False)),
    ]
    ladder, errors = [], []
    headline = None
    for name, comm in rungs:
        try:
            tr = SpmdTrainer(fresh(), optimizer.SGD(0.1),
                             nn.functional.cross_entropy, mesh, comm=comm)
            compiled = tr._step.lower(
                tr.state, tr.opt_state, jax.random.key(0), (x,), (y,)
            ).compile()
            rep = hlo_bytes.report_compiled(compiled, num_devices=n)
            grad = hlo_bytes.grad_collectives(rep)
            wire = sum(c["wire_bytes"] for c in grad)
            for _ in range(warmup):
                loss = tr.train_step(x, y)
            float(loss)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = tr.train_step(x, y)
            float(loss)
            dt = (time.perf_counter() - t0) / steps
            rung = {"mode": name, "step_ms": round(dt * 1e3, 3),
                    "collective_wire_bytes_per_step": int(wire),
                    "n_grad_collectives": len(grad),
                    "dtypes": sorted({c["dtype"] for c in grad})}
            ladder.append(rung)
            if headline is None:
                headline = rung
        except Exception as e:  # noqa: BLE001 — degrade, don't die
            msg = f"{name}: {type(e).__name__}: {e}"[:160]
            errors.append(msg)
            ladder.append({"mode": name, "error": msg})
    if headline is None:
        return {"metric": METRIC, "value": 0.0, "error": "; ".join(errors),
                "platform": devs[0].platform, "devices": n}
    out = {"metric": METRIC, "value": headline["step_ms"], "unit": "ms",
           "mode": headline["mode"],
           "collective_wire_bytes_per_step":
               headline["collective_wire_bytes_per_step"],
           "n_grad_collectives": headline["n_grad_collectives"],
           "platform": devs[0].platform, "devices": n, "ladder": ladder}
    if errors:
        out["degraded_from"] = errors
    return out


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

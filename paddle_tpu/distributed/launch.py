"""Process launcher: ``python -m paddle_tpu.distributed.launch``.

Reference: ``python/paddle/distributed/launch`` — builds a Pod/Container
job model, then a collective or PS controller spawns trainer/server
subprocesses with role env vars, restarts on elastic events, and a master
handles rendezvous (launch/controllers/*.py, job/pod.py).

TPU shape: one process per host (JAX owns all local chips), roles wired
through the same env vars the RoleMaker reads (PADDLE_TRAINER_ID,
PADDLE_TRAINERS_NUM, TRAINING_ROLE, PADDLE_PORT …), multi-host bootstrap
via ``jax.distributed.initialize`` coordinates over DCN. For the PS mode
it spawns server + trainer processes on localhost exactly like the
reference's test harness (test_dist_fleet_base.py:311 _run_cluster).

A chip belongs to one process. The launcher itself never imports jax;
with ``nproc > 1`` on one host each trainer is handed ITS OWN chip
(libtpu's ``TPU_VISIBLE_DEVICES=<rank>`` with 1x1x1 process bounds — a
rank beyond the host's chips fails in libtpu, loudly), and PS servers,
which hold host tables only, are pinned to ``JAX_PLATFORMS=cpu`` so they
never claim one.
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

__all__ = ["JobSpec", "launch_local", "elastic_launch_local", "main"]


class JobSpec:
    def __init__(self, script: List[str], nproc: int = 1, servers: int = 0,
                 coordinator_port: int = 12355, log_dir: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None) -> None:
        self.script = script
        self.nproc = nproc
        self.servers = servers
        self.coordinator_port = coordinator_port
        self.log_dir = log_dir
        self.env = env or {}


def _proc_env(spec: JobSpec, role: str, rank: int) -> Dict[str, str]:
    env = dict(os.environ)
    env.update(spec.env)
    trainer_eps = ",".join(
        f"127.0.0.1:{spec.coordinator_port + 1 + i}" for i in range(spec.nproc))
    server_eps = ",".join(
        f"127.0.0.1:{spec.coordinator_port + 100 + i}" for i in range(spec.servers))
    env.update({
        "TRAINING_ROLE": role,
        "PADDLE_TRAINERS_NUM": str(spec.nproc),
        "PADDLE_TRAINER_ENDPOINTS": trainer_eps,
        "PADDLE_PSERVERS_IP_PORT_LIST": server_eps,
        "PADDLE_COORDINATOR": f"127.0.0.1:{spec.coordinator_port}",
        "PADDLE_WORLD_SIZE": str(spec.nproc),
    })
    if role == "TRAINER":
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_RANK"] = str(rank)
        if spec.nproc > 1:
            # one chip per local trainer (ignored off-TPU); a caller
            # that partitions the chips itself says so in spec.env
            for k, v in (("TPU_VISIBLE_DEVICES", str(rank)),
                         ("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1"),
                         ("TPU_PROCESS_BOUNDS", "1,1,1")):
                if k not in spec.env:
                    env[k] = v
    else:
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_PORT"] = str(spec.coordinator_port + 100 + rank)
        env["POD_IP"] = "127.0.0.1"
        env["PADDLE_SERVER_ID"] = str(rank)
    return env


def _spawn(spec: JobSpec, role: str, rank: int,
           log_suffix: str = "") -> subprocess.Popen:
    """One trainer/server subprocess with role env + optional log file
    (shared by the plain and elastic launchers)."""
    env = _proc_env(spec, role, rank)
    stdout = None
    if spec.log_dir:
        os.makedirs(spec.log_dir, exist_ok=True)
        stdout = open(os.path.join(
            spec.log_dir, f"{role.lower()}_{rank}{log_suffix}.log"), "w")
    try:
        return subprocess.Popen(
            [sys.executable] + spec.script, env=env,
            stdout=stdout, stderr=subprocess.STDOUT if stdout else None)
    finally:
        if stdout is not None:
            stdout.close()  # the child holds its own duplicate fd


def _terminate(procs) -> None:
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            p.kill()


def launch_local(spec: JobSpec, timeout: Optional[float] = None) -> int:
    """Spawn servers then trainers on localhost; wait for trainers, then
    terminate servers (the PS controller sequence). Returns the first
    nonzero trainer exit code, else 0."""
    procs: List[subprocess.Popen] = []
    server_procs: List[subprocess.Popen] = []

    try:
        for r in range(spec.servers):
            server_procs.append(_spawn(spec, "PSERVER", r))
        for r in range(spec.nproc):
            procs.append(_spawn(spec, "TRAINER", r))
        deadline = time.monotonic() + timeout if timeout else None
        rc = 0
        for p in procs:
            left = max(0.1, deadline - time.monotonic()) if deadline else None
            code = p.wait(timeout=left)
            rc = rc or code
        return rc
    finally:
        _terminate(procs + server_procs)


def elastic_launch_local(
    spec: JobSpec,
    min_np: Optional[int] = None,
    max_np: Optional[int] = None,
    heartbeat_interval: float = 0.3,
    heartbeat_ttl: float = 1.0,
    elastic_timeout: float = 1.5,
    max_restarts: int = 3,
    timeout: Optional[float] = None,
) -> int:
    """The elastic controller loop (fleet/elastic/manager.py:439-532 +
    the launcher's restart path): supervise local trainer processes,
    heartbeat each LIVE process into the elastic store, and act on the
    ElasticManager's decision — HOLD keeps running, RESTART kills the
    survivors and relaunches every trainer with the world size and
    endpoint env REWRITTEN to the shrunken (or grown) membership
    (manager.py:465's DISTRIBUTED_TRAINER_ENDPOINTS update), ERROR gives
    up below ``min_np``. Trainer scripts are expected to resume from
    their checkpoints (io/auto_checkpoint) — restarts re-exec them.

    Returns 0 when a generation of trainers all exit cleanly; nonzero on
    ERROR / restart budget exhaustion / timeout."""
    from .elastic import ElasticManager, ElasticStatus, MemoryStore

    min_np = min_np if min_np is not None else spec.nproc
    max_np = max_np if max_np is not None else spec.nproc
    store = MemoryStore()
    deadline = time.monotonic() + timeout if timeout else None
    np_now = spec.nproc
    restarts = 0

    server_procs: List[subprocess.Popen] = []
    trainers: List[subprocess.Popen] = []

    try:
        for r in range(spec.servers):
            server_procs.append(_spawn(spec, "PSERVER", r))

        while True:
            gen_spec = JobSpec(spec.script, nproc=np_now,
                               servers=spec.servers,
                               coordinator_port=spec.coordinator_port,
                               log_dir=spec.log_dir, env=spec.env)
            trainers = [_spawn(gen_spec, "TRAINER", r, f".g{restarts}")
                        for r in range(np_now)]
            mgr = ElasticManager(store, job_id="launch", np=np_now,
                                 host="supervisor",
                                 heartbeat_interval=heartbeat_interval,
                                 heartbeat_ttl=heartbeat_ttl,
                                 elastic_timeout=elastic_timeout,
                                 min_np=min_np, max_np=max_np)
            # the supervisor beats on BEHALF of each live process —
            # process liveness is the health signal a single-host
            # controller has (multi-host nodes heartbeat themselves)
            decision = None
            while True:
                if deadline and time.monotonic() > deadline:
                    return 124
                for r, p in enumerate(trainers):
                    # a CLEAN exit keeps its membership (that rank's
                    # partition is done, not dead) — only a crash or a
                    # hang-kill stops the heartbeat and shrinks the world
                    if p.poll() is None or p.poll() == 0:
                        store.put(mgr.member_key(f"rank{r}"), "1",
                                  ttl=heartbeat_ttl)
                if all(p.poll() == 0 for p in trainers):
                    return 0  # generation completed cleanly
                status = mgr.watch_once()
                if status is ElasticStatus.RESTART:
                    # adopt_world counts store membership (live OR
                    # cleanly-finished ranks — same predicate as the
                    # heartbeats), clamps to [min_np, max_np] and
                    # publishes the endpoint rewrite (manager.py:465)
                    decision = max(mgr.adopt_world(), 1)
                    break
                if status is ElasticStatus.ERROR:
                    return 1  # unrecoverable below min_np
                if (all(p.poll() is not None for p in trainers)
                        and any(p.poll() != 0 for p in trainers)
                        and status is ElasticStatus.HOLD):
                    # whole generation gone before the ttl expired —
                    # skip the grace wait, go straight to restart
                    decision = max(min_np, 1)
                    break
                time.sleep(heartbeat_interval)

            _terminate(trainers)  # kill survivors; relaunch the world
            for r in range(np_now):
                store.delete(mgr.member_key(f"rank{r}"))
            restarts += 1
            if restarts > max_restarts:
                return 1
            np_now = decision
    finally:
        # every exit path (completion, ERROR, timeout, restart budget)
        # reaps the CURRENT generation too — no orphaned trainers
        _terminate(trainers + server_procs)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch trainers (and PS servers) on this host.")
    ap.add_argument("--nproc_per_node", type=int, default=1)
    ap.add_argument("--servers", type=int, default=0)
    ap.add_argument("--master_port", type=int, default=12355)
    ap.add_argument("--log_dir", default=None)
    ap.add_argument("script", nargs=argparse.REMAINDER,
                    help="training script and its args")
    args = ap.parse_args(argv)
    script = [a for a in args.script if a != "--"]
    if not script:
        ap.error("missing training script")
    return launch_local(JobSpec(script, nproc=args.nproc_per_node,
                                servers=args.servers,
                                coordinator_port=args.master_port,
                                log_dir=args.log_dir))


if __name__ == "__main__":
    sys.exit(main())

"""Test config: force an 8-device virtual CPU platform so sharding and
collective tests exercise real multi-device lowering without TPU hardware
(SURVEY §4 TPU translation of the localhost-subprocess harness).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Measured >5s each on the 1-core CI host (round-2 --durations run); the
# default gate (pytest.ini addopts) excludes them — run all with -m "".
_SLOW = {
    "test_tdm_learns_and_retrieves",
    "test_pass_trainer_amp_trains",
    "test_tp_grads_match_serial",
    "test_moe_ep_matches_serial",
    "test_causal_cp_matches_serial",
    "test_cp_matches_serial",
    "test_tp_matches_serial",
    "test_mobilenet_v2_shapes",
    "test_vgg11_shapes",
    "test_mobilenet_trains",
    "test_mobilenet_v1_shapes_and_scale",
    "test_vgg16_bn_shapes",
    "test_resnet50_forward_shape",
    "test_resnet18_trains",
    "test_multiprocess_cluster",
    "test_fleet_rpc_cluster",
    "test_multiprocess_failover_kill_minus_nine",
    "test_stream_trainer_survives_kill_shard_bit_identical",
    "test_ring_attention_backward_matches_full",
    "test_ring_attention_matches_full",
    "test_hybrid_moe_runs",
    "test_hybrid_loss_decreases",
    "test_hybrid_first_loss_matches_serial",
    "test_moe_single_rank_runs_and_grads",
    "test_moe_expert_parallel_matches_single_rank",
    "test_lenet_forward_and_one_step",
    "test_pipeline_training_matches_serial",
    "test_launch_local_trainers",
    "test_hybrid_save_load_resume",
    "test_pipeline_trainer_save_load_resume",
    "test_auto_checkpoint_resumes_day_stream",
    "test_train_passes_overlapped_matches_sequential",
    "test_launch_propagates_failure",
    "test_elastic_launch_restarts_and_completes",
    "test_elastic_launch_gives_up_below_min_np",
    "test_dssm_learns_pairing_and_ranks_true_doc",
    "test_sharded_key_fed_matches_row_fed",
    "test_elastic_scale_in_resumes_consistently",
    "test_hybrid_sharding_axis_shards_opt_state",
    "test_routed_hot_key_batches_fit_with_dedup",
    "test_routed_negative_sentinel_rows",
    "test_din_learns_match_signal_and_ignores_padding",
    "test_multitask_learns_both_tasks",
    "test_slab_pass_matches_single_step_pass",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name.split("[")[0] in _SLOW:
            item.add_marker(pytest.mark.slow)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def launch_two_workers(worker_src: str, tmp_path, timeout: float = 240):
    """Spawn two localhost jax.distributed worker processes running
    ``worker_src`` (argv: rank world port) and return their outputs.
    Guarantees cleanup: workers are killed on timeout or assertion
    failure — never leak distributed processes into later tests."""
    import os
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(DISTRIBUTED_WORKER_PREAMBLE + worker_src)
    procs = []
    for r in range(2):
        env = dict(os.environ,
                   PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
        env.pop("XLA_FLAGS", None)
        env.pop("JAX_PLATFORMS", None)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(r), "2", str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    try:
        for r, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
            assert f"WORKER_OK {r}" in out, out[-3000:]
    finally:
        for p in procs:  # never leak distributed workers on failure
            if p.poll() is None:
                p.kill()
    return outs


#: shared bootstrap for two-process jax.distributed worker scripts
#: (argv: rank world port); launch_two_workers prepends this to the
#: worker source so the env/config dance lives in exactly one place
DISTRIBUTED_WORKER_PREAMBLE = """
import os, sys
import numpy as np

rank = int(sys.argv[1]); world = int(sys.argv[2]); port = sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["RANK"] = str(rank)
os.environ["WORLD_SIZE"] = str(world)
os.environ["PADDLE_MASTER"] = f"127.0.0.1:{port}"
import jax
jax.config.update("jax_platforms", "cpu")

from paddle_tpu.distributed import collective as C

env = C.init_parallel_env()
assert env.rank == rank and env.world_size == world
assert len(jax.devices()) == world * 4, len(jax.devices())
"""

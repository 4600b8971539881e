"""Drive the multi-device integration checks in an isolated subprocess so
the main pytest process keeps the single real CPU device (the dry-run's 512
placeholder devices are likewise process-local)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


def run_checks(*names, timeout=900):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"          # virtual host devices only
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "distributed_checks.py"), *names],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


@pytest.mark.parametrize("check", [
    "check_expert_parallel_schedules",
    "check_a2a_pipelined_token_exact",
    "check_padded_experts_dead_on_mesh",
    "check_expert_replication_overlap",
    "check_serving_engine_on_mesh",
    "check_quantized_weights_on_mesh",
    "check_cp_decode_int8_cache",
    "check_cp_decode_matches_single_device",
    "check_cp_decode_ring_window",
    "check_sharded_train_step_matches_single",
    "check_params_pspec_structure",
    "check_data_sharded_batch",
    "check_analysis_rules_on_mesh",
])
def test_distributed(check):
    out = run_checks(check)
    assert f"PASS {check.replace('check_', '').split('_matches')[0]}" in out \
        or "ALL_OK" in out

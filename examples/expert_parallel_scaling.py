"""Expert-parallel scaling demo (paper §5.3 in miniature): run the same MoE
forward under 1/2/4/8-way expert parallelism on host devices and verify the
outputs agree while per-shard expert work shrinks.

    PYTHONPATH=src python examples/expert_parallel_scaling.py
(uses XLA host-device emulation; run standalone, not under the test runner)
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import expert_parallel, router as router_lib
from repro.core.dynamic_load import simulate_expected_experts
from repro.launch.mesh import make_mesh


def main():
    # reduced dims but the paper's true 16-expert arithmetic so 8-way EP divides
    cfg = get_config("dbrx").reduced().replace(
        capacity_factor=8.0, num_experts=16, num_experts_padded=16,
        experts_per_token=4)
    key = jax.random.PRNGKey(0)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts_padded
    layer_p = {
        "router": jax.random.normal(key, (d, e)) * 0.1,
        "experts": {
            "w_gate": jax.random.normal(jax.random.fold_in(key, 1), (e, d, f)) * 0.05,
            "w_up": jax.random.normal(jax.random.fold_in(key, 2), (e, d, f)) * 0.05,
            "w_down": jax.random.normal(jax.random.fold_in(key, 3), (e, f, d)) * 0.05,
        },
    }
    x = jax.random.normal(jax.random.fold_in(key, 4), (4, 16, d))

    ref = None
    for n_model in (1, 2, 4, 8):
        if n_model == 1:
            y, aux, _ = expert_parallel.moe_layer(cfg, None, layer_p, x)
        else:
            mesh = make_mesh((8 // n_model, n_model), ("data", "model"))
            y, aux, _ = expert_parallel.moe_layer(cfg, mesh, layer_p, x)
        y = np.asarray(y, np.float32)
        if ref is None:
            ref = y
        err = np.max(np.abs(y - ref))
        print(f"EP={n_model}: experts/shard={e // n_model:2d} "
              f"maxerr vs 1-way={err:.2e}")

    print("\nE[#exec experts/node/layer] (paper Table 1 statistic, "
          "uniform routing):")
    for n in (2, 3, 4):
        v = simulate_expected_experts(16, 4, n, n_tokens=400)
        print(f"  {n} nodes: {v:.2f}   (paper measured: "
              f"{ {2: 2.65, 3: 2.32, 4: 1.57}[n] })")


if __name__ == "__main__":
    main()

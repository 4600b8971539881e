"""Paper Table 4: P-L_R-D scalability across 2..8-way expert parallelism.

Runs in a subprocess with 8 emulated host devices: the reduced DBRX decode
step under EP degrees 1/2/4/8.  Reports wall-clock (noisy on CPU, indicative
only), per-shard expert FLOPs from the HLO (the paper's 'MoE time' driver —
decreases with nodes) and collective bytes (the paper's 'Comm.' share —
grows with nodes).  CPU-only by design: the child is pinned to the CPU
backend, so it never competes with its parent for an accelerator.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import markdown_table, save_result

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
from benchmarks.common import time_fn
from repro.configs.base import get_config
from repro.launch import hlo
from repro.launch.mesh import make_mesh
from repro.models.model import build_model

base = get_config("dbrx").reduced().replace(
    moe_strategy="dispatch", expert_parallel="decentralized",
    num_experts=16, num_experts_padded=16, experts_per_token=4)
b = 8
out = {}
for ep in (1, 2, 4, 8):
    mesh = None if ep == 1 else make_mesh((8 // ep, ep), ("data", "model"))
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(b, 64)
    step = {"tokens": jnp.zeros((b, 1), jnp.int32),
            "lengths": jnp.full((b,), 8, jnp.int32)}
    fn = jax.jit(lambda p, c, s: model.decode_step(p, c, s, mesh))
    t = time_fn(fn, params, cache, step, iters=6)
    totals = hlo.analyze(fn.lower(params, cache, step).compile().as_text())
    out[str(ep)] = {
        "decode_s": t,
        "hlo_flops_per_device": totals.flops,
        "collective_bytes_per_device": totals.collective_bytes,
        "collectives": dict(totals.coll),
    }
print("JSON:" + json.dumps(out))
"""


def run() -> dict:
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), os.path.join(here, ".."),
         env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, timeout=1200,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON:")][0]
    rows = json.loads(line[5:])

    # paper Table 4 mechanism: per-node expert work decreases with nodes
    assert rows["8"]["hlo_flops_per_device"] < rows["1"]["hlo_flops_per_device"]
    rows["_meta"] = {
        "paper_table4": {2: 6.1, 3: 6.5, 4: 7.0},
        "note": "CPU wall-clock indicative; FLOPs/device and collective "
                "bytes are deterministic HLO measurements",
    }
    save_result("table4_scalability", rows)
    return rows


def render(rows: dict) -> str:
    hdr = ["EP degree", "decode s/step (CPU)", "expert FLOPs/device",
           "collective B/device"]
    body = [[ep, f"{v['decode_s']*1e3:.1f} ms",
             f"{v['hlo_flops_per_device']:.3g}",
             f"{v['collective_bytes_per_device']:.3g}"]
            for ep, v in sorted(rows.items()) if not ep.startswith("_")]
    return markdown_table(hdr, body)


if __name__ == "__main__":
    print(render(run()))

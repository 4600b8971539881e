import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh without allocating a single real buffer.

For each pair this proves: the sharding config is coherent (no mismatched
collectives), the program fits per-device HBM (memory_analysis), and it
yields the roofline inputs (FLOPs / bytes / collective bytes with
while-loop trip multipliers via launch/hlo.py).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out results/

The 512 placeholder host devices are forced by the XLA_FLAGS line ABOVE ANY
IMPORT — smoke tests and benches never import this module.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import optim
from repro.configs.base import ARCH_IDS, SHAPES, get_config, input_specs
from repro.launch import hlo as hlo_lib
from repro.launch import sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12       # bf16
HBM_BW = 819e9            # bytes/s
ICI_BW = 50e9             # bytes/s per link


def abstract_params(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def make_train_step(model, mesh, ocfg=None):
    ocfg = ocfg or optim.OptimizerConfig()

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch, mesh)
        params, opt_state, om = optim.update(ocfg, grads, opt_state, params)
        return params, opt_state, {**metrics, **om, "loss": loss}

    return train_step


def make_prefill_step(model, mesh):
    def prefill_step(params, batch, cache):
        return model.prefill(params, batch, cache, mesh)
    return prefill_step


def make_serve_step(model, mesh, context_len):
    def serve_step(params, cache, batch):
        return model.decode_step(params, cache, batch, mesh,
                                 context_len=context_len)
    return serve_step


def lower_pair(arch: str, shape_name: str, mesh, cfg_overrides=None):
    """Lower + compile one (arch, shape) on ``mesh``. Returns (compiled, cfg)."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = cfg.replace(**cfg_overrides)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    p_sds = abstract_params(model)
    batch_sds = input_specs(cfg, shape)

    p_spec = sharding.params_pspec(
        cfg, mesh, p_sds, mode="train" if shape.kind == "train" else "serve")
    b_spec = sharding.batch_pspec(cfg, mesh, batch_sds)
    n_p = sharding.named(mesh, p_spec)
    n_b = sharding.named(mesh, b_spec)

    with mesh:
        if shape.kind == "train":
            o_sds = jax.eval_shape(optim.init, p_sds)
            o_spec = sharding.opt_pspec(cfg, mesh, o_sds, p_spec)
            n_o = sharding.named(mesh, o_spec)
            fn = jax.jit(make_train_step(model, mesh),
                         in_shardings=(n_p, n_o, n_b),
                         out_shardings=(n_p, n_o, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(p_sds, o_sds, batch_sds)
        elif shape.kind == "prefill":
            c_sds = model.cache_specs(shape.global_batch, shape.seq_len)
            c_spec = sharding.cache_pspec(cfg, mesh, c_sds)
            n_c = sharding.named(mesh, c_spec)
            fn = jax.jit(make_prefill_step(model, mesh),
                         in_shardings=(n_p, n_b, n_c),
                         out_shardings=(None, n_c),
                         donate_argnums=(2,))
            lowered = fn.lower(p_sds, batch_sds, c_sds)
        else:  # decode
            c_sds = model.cache_specs(shape.global_batch, shape.seq_len)
            c_spec = sharding.cache_pspec(cfg, mesh, c_sds)
            n_c = sharding.named(mesh, c_spec)
            fn = jax.jit(make_serve_step(model, mesh, shape.seq_len),
                         in_shardings=(n_p, n_c, n_b),
                         out_shardings=(None, n_c),
                         donate_argnums=(1,))
            lowered = fn.lower(p_sds, c_sds, batch_sds)
        compiled = lowered.compile()
    return compiled, cfg


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N·D per decoded/prefilled token
    (N = active params for MoE)."""
    n = cfg.n_active_params() if cfg.is_moe else cfg.n_params()
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.kind in ("train", "prefill") else 1)
    return (6.0 if shape.kind == "train" else 2.0) * n * tokens


def roofline(totals: hlo_lib.Totals, n_devices: int, cfg, shape) -> dict:
    """Three roofline terms (seconds). HLO numbers are per-device, so terms
    are per-device time = total work / (chips × per-chip rate).  Memory uses
    convert-adjusted bytes (the CPU backend's bf16->f32 upcasts don't exist
    on TPU)."""
    t_comp = totals.flops / PEAK_FLOPS
    t_mem = totals.hbm_bytes / HBM_BW
    t_coll = totals.collective_bytes / ICI_BW
    dom = max(("compute", t_comp), ("memory", t_mem), ("collective", t_coll),
              key=lambda kv: kv[1])[0]
    mf = model_flops(cfg, shape)
    return {
        "compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
        "dominant": dom,
        "model_flops": mf,
        "hlo_flops_per_device": totals.flops,
        "useful_flop_ratio": mf / max(totals.flops * n_devices, 1.0),
        "collective_by_kind": dict(totals.coll),
    }


_UPCAST_RE = None


def cpu_upcast_bytes(hlo_text: str) -> float:
    """Bytes of large f32 buffers produced by ``convert`` of bf16 stacks —
    the CPU backend's whole-array upcasts (>=32 MiB) that a TPU build would
    not allocate.  Used to adjust the peak-memory estimate."""
    import re as _re
    global _UPCAST_RE
    if _UPCAST_RE is None:
        _UPCAST_RE = _re.compile(r"= f32\[([0-9,]+)\][^=]*\bconvert\(")
    total = 0.0
    for m in _UPCAST_RE.finditer(hlo_text):
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n * 4 >= 32 * 2**20:
            total += n * 4
    return total


def run_pair(arch: str, shape_name: str, multi_pod: bool,
             cfg_overrides=None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(s) for s in mesh.devices.shape),
           "n_devices": n_dev, "ok": False}
    try:
        compiled, cfg = lower_pair(arch, shape_name, mesh, cfg_overrides)
        ma = compiled.memory_analysis()
        hlo_text = compiled.as_text()
        totals = hlo_lib.analyze(hlo_text)
        upcast = cpu_upcast_bytes(hlo_text)
        rec.update({
            "ok": True,
            "compile_s": round(time.time() - t0, 1),
            "memory": {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                # memory_analysis reports the per-device SPMD program
                "peak_per_device": int(ma.argument_size_in_bytes
                                       + ma.temp_size_in_bytes
                                       + ma.output_size_in_bytes
                                       - ma.alias_size_in_bytes),
                # f32 copies of bf16 stacks made by the CPU backend's upcast
                # pass (hoisted out of the layer loop) — absent on TPU
                "cpu_upcast_bytes": int(upcast),
                # clamped below by live arguments + outputs (converts of
                # freed buffers would otherwise over-subtract)
                "peak_per_device_tpu_est": int(max(
                    ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes
                    - upcast,
                    ma.argument_size_in_bytes + ma.output_size_in_bytes
                    - ma.alias_size_in_bytes)),
            },
            "hlo": {
                "flops_per_device": totals.flops,
                "bytes_per_device_raw": totals.bytes,
                "hbm_bytes_per_device": totals.hbm_bytes,
                "convert_bytes_per_device": totals.convert_bytes,
                "collective_bytes_per_device": totals.collective_bytes,
                "collective_by_kind": dict(totals.coll),
            },
            "roofline": roofline(totals, n_dev, cfg, SHAPES[shape_name]),
        })
        try:
            ca = compiled.cost_analysis()
            rec["xla_cost_analysis"] = {
                "flops_body_once": float(ca.get("flops", -1.0)),
                "bytes_body_once": float(ca.get("bytes accessed", -1.0)),
            }
        except Exception:
            pass
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--override", default=None,
                    help="JSON dict of ModelConfig overrides (perf exps)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    enable_compile_cache()
    overrides = json.loads(args.override) if args.override else None
    pairs = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))

    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for arch, shape_name in pairs:
        rec = run_pair(arch, shape_name, args.multi_pod, overrides)
        tag = ("-" + args.tag) if args.tag else ""
        fname = f"{arch.replace('-', '_')}_{shape_name}_{rec['mesh']}{tag}.json"
        with open(os.path.join(args.out, fname), "w") as f:
            json.dump(rec, f, indent=1)
        if rec["ok"]:
            r = rec["roofline"]
            print(f"OK   {arch:24s} {shape_name:12s} {rec['mesh']:10s} "
                  f"compute={r['compute_s']:.2e}s memory={r['memory_s']:.2e}s "
                  f"coll={r['collective_s']:.2e}s dom={r['dominant']:10s} "
                  f"peak/dev={rec['memory']['peak_per_device_tpu_est']/2**30:.2f}GiB"
                  f"(raw {rec['memory']['peak_per_device']/2**30:.1f}) "
                  f"[{rec['compile_s']}s]", flush=True)
        else:
            n_fail += 1
            print(f"FAIL {arch:24s} {shape_name:12s} {rec['mesh']:10s} "
                  f"{rec['error']}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Training launcher: real steps on whatever devices exist.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \
        --reduced --steps 50 --batch 8 --seq 128

On the CPU container this runs reduced configs end-to-end (the examples/
drivers call into here); on a real TPU slice the same entry point takes the
full configs with the production mesh (--mesh data,model=...).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.ckpt import io as ckpt_io
from repro.configs.base import get_config
from repro.data.pipeline import Pipeline, PipelineConfig, shard_batch
from repro.launch import sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model


def make_train_step(model, ocfg, mesh):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch, mesh)
        params, opt_state, om = optim.update(ocfg, grads, opt_state, params)
        return params, opt_state, {**metrics, **om, "loss": loss}
    return train_step


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          mesh=None, lr: float = 3e-4, log_every: int = 10,
          ckpt_path: str | None = None, seed: int = 0):
    model = build_model(cfg)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    ocfg = optim.OptimizerConfig(lr=lr, total_steps=steps,
                                 warmup_steps=max(steps // 20, 5))
    opt_state = optim.init(params)

    if mesh is not None:
        p_spec = sharding.params_pspec(cfg, mesh, params, mode="train")
        params = jax.device_put(params, sharding.named(mesh, p_spec))
        o_spec = sharding.opt_pspec(cfg, mesh, opt_state, p_spec)
        opt_state = jax.device_put(opt_state, sharding.named(mesh, o_spec))

    step_fn = jax.jit(make_train_step(model, ocfg, mesh),
                      donate_argnums=(0, 1))

    pipe = Pipeline(PipelineConfig(seq_len=seq_len, global_batch=global_batch,
                                   vocab_size=cfg.vocab_size, seed=seed))
    history = []
    t0 = time.time()
    for step in range(steps):
        np_batch = pipe.next_batch()
        if cfg.family == "audio":
            # frontend stub: frame embeddings instead of token ids
            b, s = np_batch["tokens"].shape
            emb = np.take(np.asarray(jax.device_get(params["embed"]))
                          if not isinstance(params["embed"], jnp.ndarray)
                          else np.asarray(params["embed"], np.float32),
                          np_batch["tokens"] % cfg.vocab_size, axis=0)
            batch = {"frame_embeds": jnp.asarray(emb, cfg.dtype_jnp),
                     "labels": jnp.asarray(np_batch["labels"])}
        else:
            batch = {k: jnp.asarray(v) for k, v in np_batch.items()}
        if mesh is not None:
            batch = shard_batch(batch, mesh)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % log_every == 0 or step == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"], m["wall_s"] = step, round(time.time() - t0, 2)
            history.append(m)
            print(f"step {step:5d} loss {m['loss']:.4f} ce {m['ce']:.4f} "
                  f"aux {m.get('aux', 0.0):.4f} lr {m['lr']:.2e} "
                  f"gnorm {m['grad_norm']:.3f} [{m['wall_s']}s]", flush=True)
    if ckpt_path:
        ckpt_io.save(ckpt_path, params, step=steps)
        print(f"saved checkpoint to {ckpt_path}")
    return params, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--moe-strategy", default=None,
                    choices=[None, "dense", "dispatch"])
    ap.add_argument("--expert-parallel", default=None,
                    choices=[None, "centralized", "decentralized", "a2a"])
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    over = {}
    if args.moe_strategy:
        over["moe_strategy"] = args.moe_strategy
    if args.expert_parallel:
        over["expert_parallel"] = args.expert_parallel
    if over:
        cfg = cfg.replace(**over)
    train(cfg, steps=args.steps, global_batch=args.batch, seq_len=args.seq,
          lr=args.lr, ckpt_path=args.ckpt)


if __name__ == "__main__":
    main()

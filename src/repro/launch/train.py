"""End-to-end LM training launcher.

CPU-runnable for reduced configs (examples/train_lm.py drives a ~100M
model for a few hundred steps); on a real pod the same code path uses the
production mesh and full configs.

  PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
      --reduced --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

The paper's PIM-ML workloads (LIN/LOG/DTR/KME) launch through the
workload-session CLI instead: ``python -m repro.launch.pim_ml`` (built on
the unified repro.api surface — registry, PimDataset, ReduceStrategy).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.data.tokens import MarkovCorpus
from repro.models.api import Model
from repro.optim.adam import AdamW
from repro.train import checkpoint as ckpt_lib
from repro.train.fault_tolerance import StragglerMonitor
from repro.train.loop import make_train_step


def build(arch: str, *, reduced: bool, lr: float = 3e-4,
          microbatches: int = 1, quantize_dense: bool = False,
          lut_activations: bool = False, overrides: dict | None = None):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced(**(overrides or {}))
    if quantize_dense or lut_activations:
        cfg = dataclasses.replace(cfg, quantize_dense=quantize_dense,
                                  lut_activations=lut_activations)
    model = Model(cfg)
    opt = AdamW(lr=lr)
    step_fn = jax.jit(make_train_step(model, opt,
                                      microbatches=microbatches),
                      donate_argnums=(0, 1))
    return cfg, model, opt, step_fn


def train(arch: str, *, steps: int, batch: int, seq: int,
          reduced: bool = True, ckpt_dir: str = "", ckpt_every: int = 50,
          lr: float = 3e-4, seed: int = 0, microbatches: int = 1,
          log_every: int = 10, resume: bool = True,
          quantize_dense: bool = False, lut_activations: bool = False,
          overrides: dict | None = None):
    cfg, model, opt, step_fn = build(
        arch, reduced=reduced, lr=lr, microbatches=microbatches,
        quantize_dense=quantize_dense, lut_activations=lut_activations,
        overrides=overrides)
    params = model.init(jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    corpus = MarkovCorpus(cfg.vocab_size, seed=seed)
    start = 0
    if ckpt_dir and resume:
        last = ckpt_lib.latest_step(ckpt_dir)
        if last is not None:
            state = ckpt_lib.restore(ckpt_dir, last,
                                     (params, opt_state))
            params, opt_state = state
            start = last
            print(f"resumed from step {last}")

    monitor = StragglerMonitor()
    losses = []
    t_start = time.perf_counter()
    for step in range(start, steps):
        batch_np = corpus.batch(batch, seq)
        if cfg.family == "vlm":
            batch_np["vision"] = np.random.RandomState(step).normal(
                0, 1, (batch, cfg.vision_tokens, cfg.vision_dim)
            ).astype(np.float32 if cfg.dtype == "float32" else np.float32)
        if cfg.family == "audio":
            batch_np["frames"] = np.random.RandomState(step).normal(
                0, 1, (batch, cfg.encoder_seq, cfg.d_model)
            ).astype(np.float32)
        batch_dev = jax.tree_util.tree_map(jnp.asarray, batch_np)
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch_dev)
        loss = float(metrics["loss"])
        monitor.observe(time.perf_counter() - t0)
        losses.append(loss)
        if (step + 1) % log_every == 0 or step == start:
            tput = batch * seq * log_every / max(
                time.perf_counter() - t_start, 1e-9)
            t_start = time.perf_counter()
            print(f"step {step + 1:5d}  loss {loss:7.4f}  "
                  f"gnorm {float(metrics['grad_norm']):7.3f}  "
                  f"~{tput_fmt(tput)} tok/s")
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt_lib.save(ckpt_dir, step + 1, (params, opt_state))
    return params, losses, corpus


def tput_fmt(x: float) -> str:
    return f"{x/1e3:.1f}k" if x > 1e3 else f"{x:.0f}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--quantize-dense", action="store_true",
                    help="paper technique: int8 linear layers")
    ap.add_argument("--lut-activations", action="store_true",
                    help="paper technique: LUT activations")
    args = ap.parse_args()
    train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
          reduced=args.reduced, ckpt_dir=args.ckpt_dir,
          ckpt_every=args.ckpt_every, lr=args.lr,
          microbatches=args.microbatches,
          quantize_dense=args.quantize_dense,
          lut_activations=args.lut_activations)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

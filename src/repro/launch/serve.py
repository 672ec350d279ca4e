"""Serving launcher: batched generation with the slot engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --requests 8
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.models.api import Model
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b", choices=list(ARCH_IDS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-seq", type=int, default=128)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, n_slots=args.slots,
                         max_seq=args.max_seq)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab_size,
                                       args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    out = engine.run(reqs)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in out)
    print(f"served {len(out)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    for i, r in enumerate(out[:3]):
        print(f"req{i}: prompt={r.prompt[:8].tolist()}... "
              f"output={r.output[:12]}...")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

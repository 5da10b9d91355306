"""Serving launcher: ``python -m repro.launch.serve --arch <id> --requests N``.

Batched greedy decoding with the LITS exact-prefix prompt cache; repeated
prompts skip prefill entirely (the paper's index on the serving hot path).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.base import IndexRuntimeConfig
from repro.configs.registry import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LMModel
from repro.serve.engine import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--repeat-frac", type=float, default=0.5,
                    help="fraction of repeated prompts (prefix-cache hits)")
    ap.add_argument("--max-len", type=int, default=512,
                    help="KV window bound: prompt + generation + 1 must fit "
                         "(validated per request, never silently clamped)")
    ap.add_argument("--cache-capacity", type=int, default=1024,
                    help="prefix-cache slots; past this, LRU eviction via "
                         "the index DELETE path")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.decoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode path")
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    runtime = IndexRuntimeConfig.from_env().validate()
    eng = ServeEngine(model, params, index_backend=runtime.search_backend,
                      cache_capacity=args.cache_capacity,
                      max_len=args.max_len)
    rng = np.random.default_rng(0)
    base = rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len)).astype(np.int32)
    t0 = time.time()
    for r in range(args.requests):
        if rng.random() < args.repeat_frac and r > 0:
            prompts = base  # repeated -> LITS cache hit
        else:
            prompts = rng.integers(0, cfg.vocab,
                                   size=(args.batch, args.prompt_len)).astype(np.int32)
        out = eng.generate(prompts, n_steps=args.gen)
    wall = time.time() - t0
    s = eng.stats
    pc = eng.prefix_cache.stats
    print(f"{args.requests} request batches ({args.batch}x{args.prompt_len}+{args.gen}) "
          f"in {wall:.2f}s")
    print(f"prefills={s.prefills} cached_prefills={s.cached_prefills} "
          f"decode_steps={s.decode_steps}")
    print(f"prefix-cache hit_rate={pc.hit_rate:.2f} inserts={pc.inserts} "
          f"evictions={pc.evictions} merges={pc.merges}")
    # the request plane under the cache (DESIGN.md §9)
    sv = eng.prefix_cache.service.stats()
    print(f"index-service flushes={sv.flushes} "
          f"coalescing={sv.coalescing_factor:.1f} ops/dispatch "
          f"p50={sv.p50_ms:.2f}ms p99={sv.p99_ms:.2f}ms "
          f"queue_wait={sv.mean_queue_wait_ms:.2f}ms "
          f"flush={sv.mean_flush_ms:.2f}ms "
          f"syncs/flush={sv.syncs_per_flush:.2f} "
          f"shed={sv.shed} maintenance_merges={sv.merges}")


if __name__ == "__main__":
    main()

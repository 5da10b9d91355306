"""Serve a small model with batched requests; LITS is the prompt-prefix cache.

    PYTHONPATH=src python examples/serve_prefix_cache.py
"""
import time

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.index import IndexConfig
from repro.models import LMModel
from repro.serve.engine import ServeEngine


def main() -> None:
    cfg = get_arch("h2o-danube-3-4b").reduced()
    model = LMModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # one IndexConfig drives the prompt-cache index end to end (DESIGN.md §8):
    # width, delta sizing and the auto-compaction threshold
    eng = ServeEngine(model, params,
                      index_config=IndexConfig(width=256, delta_capacity=1024,
                                               auto_merge_threshold=0.75))
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, cfg.vocab, size=(4, 16)).astype(np.int32) for _ in range(3)]

    print("wave 1 (cold) ...")
    t0 = time.time()
    for b in batches:
        eng.generate(b, n_steps=8)
    cold = time.time() - t0
    print(f"  prefills={eng.stats.prefills} cached={eng.stats.cached_prefills} "
          f"wall={cold:.2f}s")

    print("wave 2 (same prompts, LITS exact-prefix hits) ...")
    t0 = time.time()
    for b in batches:
        eng.generate(b, n_steps=8)
    warm = time.time() - t0
    pc = eng.prefix_cache.stats
    print(f"  prefills={eng.stats.prefills} cached={eng.stats.cached_prefills} "
          f"wall={warm:.2f}s  speedup={cold / max(warm, 1e-9):.2f}x")
    print(f"  prefix-cache: hit_rate={pc.hit_rate:.2f} inserts={pc.inserts} "
          f"merges={pc.merges}")


if __name__ == "__main__":
    main()

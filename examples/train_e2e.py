"""End-to-end training driver: deterministic pipeline -> sharded train loop ->
checkpoint/restart, with the LITS record store deduplicating the corpus.

    PYTHONPATH=src python examples/train_e2e.py --preset tiny --steps 200
    PYTHONPATH=src python examples/train_e2e.py --preset 100m --steps 300   # ~100M params

The tiny preset runs in ~a minute on CPU; 100m is the real driver shape.
"""
import argparse
import dataclasses

import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.data.pipeline import PipelineConfig, RecordStore, TokenPipeline
from repro.index import IndexConfig
from repro.models import LMModel
from repro.train.optimizer import AdamWConfig
from repro.train.train_loop import TrainConfig, train


def preset_cfg(preset: str):
    base = get_arch("deepseek-7b")
    if preset == "tiny":
        return base.reduced()
    if preset == "100m":
        return dataclasses.replace(
            base, name="deepseek-100m", n_layers=12, d_model=768, n_heads=12,
            n_kv_heads=4, head_dim=64, d_ff=2048, vocab=32000, tp=1)
    raise KeyError(preset)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    cfg = preset_cfg(args.preset)
    model = LMModel(cfg)
    print(f"arch={cfg.name} params={cfg.param_count() / 1e6:.1f}M")

    # LITS in the data path: dedup incoming shard manifests by string id
    # (StringIndex facade underneath; IndexConfig sizes the delta)
    store = RecordStore([b"shard-%05d" % i for i in range(1000)],
                        config=IndexConfig(delta_capacity=512))
    incoming = [b"shard-%05d" % i for i in range(990, 1010)]
    fresh = store.dedup(incoming)
    print(f"record-store dedup: {int(fresh.sum())}/{len(incoming)} shards are new")

    pipe = TokenPipeline(PipelineConfig(vocab=cfg.vocab, seq_len=args.seq,
                                        global_batch=args.batch))
    opt = AdamWConfig(lr=3e-4, state_dtype=jnp.float32,
                      warmup_steps=20, total_steps=args.steps)
    tcfg = TrainConfig(steps=args.steps, ckpt_every=max(args.steps // 4, 10),
                       ckpt_dir=args.ckpt_dir, log_every=10)

    def log(step, m):
        if step % 10 == 0:
            print(f"step {step:4d}  loss={m['loss']:.4f}  gnorm={m['grad_norm']:.3f}  "
                  f"lr={m['lr']:.2e}  {m['step_time_s'] * 1e3:.0f} ms")

    out = train(model, pipe.batch_at, opt, tcfg, on_step=log)
    hist = out["history"]
    print(f"resumed_from={out['resumed_from']}  "
          f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}  "
          f"stragglers={hist[-1]['stragglers']}")


if __name__ == "__main__":
    main()

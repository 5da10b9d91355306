"""Checkpoint/restart, failure injection, deterministic replay, elastic re-mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.models import LMModel
from repro.train import checkpoint as ckpt
from repro.train.optimizer import AdamWConfig
from repro.train.train_loop import TrainConfig, train


@pytest.fixture(scope="module")
def setup():
    r = ARCHS["deepseek-7b"].reduced()
    m = LMModel(r)
    pipe = TokenPipeline(PipelineConfig(vocab=r.vocab, seq_len=16, global_batch=4))
    opt = AdamWConfig(lr=1e-3, state_dtype=jnp.float32, warmup_steps=2, total_steps=20)
    return m, pipe, opt


def _leaves(t):
    return [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(t)]


def test_checkpoint_roundtrip(tmp_path, setup):
    m, pipe, opt = setup
    params = m.init(jax.random.PRNGKey(0))
    from repro.train.optimizer import init_state

    state = {"params": params, "opt": init_state(params, opt)}
    ckpt.save(str(tmp_path), 7, state)
    restored, meta = ckpt.restore_latest(str(tmp_path), state)
    assert meta["step"] == 7
    for a, b in zip(_leaves(state), _leaves(restored)):
        assert np.array_equal(a, b)


def test_checkpoint_rotation(tmp_path, setup):
    m, pipe, opt = setup
    params = {"w": jnp.zeros((4,))}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, params, keep=2)
    assert ckpt.list_steps(str(tmp_path)) == [4, 5]


def test_crash_resume_bitwise_identical(tmp_path, setup):
    """Kill at step 6, restart, final params == uninterrupted run."""
    m, pipe, opt = setup
    d1 = str(tmp_path / "run_crash")
    d2 = str(tmp_path / "run_clean")
    t_crash = TrainConfig(steps=10, ckpt_every=3, ckpt_dir=d1, fail_at_step=7)
    with pytest.raises(RuntimeError, match="injected failure"):
        train(m, pipe.batch_at, opt, t_crash)
    # restart (no fail) — resumes from step 6 checkpoint
    t_resume = TrainConfig(steps=10, ckpt_every=3, ckpt_dir=d1)
    out_resumed = train(m, pipe.batch_at, opt, t_resume)
    assert out_resumed["resumed_from"] == 6
    # uninterrupted reference
    t_clean = TrainConfig(steps=10, ckpt_every=3, ckpt_dir=d2)
    out_clean = train(m, pipe.batch_at, opt, t_clean)
    for a, b in zip(_leaves(out_resumed["params"]), _leaves(out_clean["params"])):
        assert np.array_equal(a, b), "resume must replay identically"


def test_loss_decreases(setup):
    m, pipe, _ = setup
    opt = AdamWConfig(lr=3e-3, state_dtype=jnp.float32, warmup_steps=3,
                      total_steps=60, min_lr_frac=1.0)
    out = train(m, pipe.batch_at, opt, TrainConfig(steps=50))
    first = np.mean([h["loss"] for h in out["history"][:5]])
    last = np.mean([h["loss"] for h in out["history"][-5:]])
    assert last < first - 0.05, f"no learning: {first} -> {last}"


def test_pipeline_determinism():
    cfg = PipelineConfig(vocab=128, seq_len=8, global_batch=4, seed=9)
    p1, p2 = TokenPipeline(cfg), TokenPipeline(cfg)
    for s in (0, 5, 11):
        b1, b2 = p1.batch_at(s), p2.batch_at(s)
        assert np.array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(p1.batch_at(1)["tokens"], p1.batch_at(2)["tokens"])


def test_pipeline_host_sharding():
    full = TokenPipeline(PipelineConfig(vocab=64, seq_len=8, global_batch=8, seed=1))
    parts = [
        TokenPipeline(PipelineConfig(vocab=64, seq_len=8, global_batch=8, seed=1,
                                     host_id=h, n_hosts=2))
        for h in range(2)
    ]
    rows = [p.batch_at(3)["tokens"].shape[0] for p in parts]
    assert rows == [4, 4]


def test_elastic_reshard(setup):
    """Live state moves onto a different mesh layout (elastic scaling path)."""
    from repro.train.train_loop import reshard
    from jax.sharding import NamedSharding, PartitionSpec as P

    m, pipe, opt = setup
    params = m.init(jax.random.PRNGKey(0))
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    sh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params)
    moved = reshard(params, sh)
    for a, b in zip(_leaves(params), _leaves(moved)):
        assert np.array_equal(a, b)

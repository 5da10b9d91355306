"""int8 gradient compression + error feedback: numerics and convergence parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import dequantize, quantize


def test_quantize_roundtrip_error_bounded(rng):
    g = jnp.asarray(rng.normal(size=(256, 64)).astype(np.float32))
    q, scale = quantize(g)
    err = np.abs(np.asarray(dequantize(q, scale) - g))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_quantize_preserves_zero_and_sign():
    g = jnp.asarray([[-1.0, 0.0, 1.0, 0.5]])
    q, scale = quantize(g)
    dq = np.asarray(dequantize(q, scale))
    assert dq[0, 1] == 0.0
    assert dq[0, 0] < 0 < dq[0, 2]


def test_error_feedback_converges_sgd(rng):
    """EF-SGD on a quadratic: compressed path reaches the optimum."""
    w_true = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))
    X = jnp.asarray(rng.normal(size=(256, 32)).astype(np.float32))
    y = X @ w_true

    def loss(w):
        return jnp.mean((X @ w - y) ** 2)

    w = jnp.zeros(32)
    e = jnp.zeros(32)
    gl = jax.jit(jax.grad(loss))
    for _ in range(300):
        g = gl(w) + e
        q, s = quantize(g)
        g_hat = dequantize(q, s)
        e = g - g_hat
        w = w - 0.05 * g_hat
    assert float(loss(w)) < 1e-3


def test_compressed_dp_step_single_device():
    """shard_map compressed DP step runs on a 1-device mesh and learns."""
    from repro.configs.registry import ARCHS
    from repro.distributed.compression import init_error_state, make_compressed_dp_step
    from repro.models import LMModel
    from repro.train.optimizer import AdamWConfig, init_state

    r = ARCHS["chatglm3-6b"].reduced()
    m = LMModel(r)
    params = m.init(jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=1e-3, state_dtype=jnp.float32, warmup_steps=1, total_steps=50)
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    step = make_compressed_dp_step(m, opt_cfg, mesh)
    opt_state = init_state(params, opt_cfg)
    err = init_error_state(params)
    rng = np.random.default_rng(0)
    losses = []
    toks = rng.integers(0, r.vocab, size=(2, 16), dtype=np.int64)
    batch = {"tokens": jnp.asarray(toks, jnp.int32), "labels": jnp.asarray(toks, jnp.int32)}
    for i in range(15):
        params, opt_state, err, metrics = step(params, opt_state, err, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5

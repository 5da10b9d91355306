"""Dry-run machinery: collective parser, analytic-roofline validation, and a
small-mesh lower+compile smoke in a subprocess."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch.dryrun import parse_collectives
from repro.launch.roofline import analytic_flops, flops_per_token
from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS


def test_parse_collectives_counts_ops():
    hlo = """
  %ag = bf16[32,1024]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[256]{0} all-reduce(%y), replica_groups={{0,1},{2,3}}, to_apply=%add
  %rs = f32[64,128]{1,0} reduce-scatter(%z), replica_groups={{0,1,2,3}}, dimensions={0}
  %a2a = bf16[8,16]{1,0} all-to-all(%w), replica_groups={{0,1,2,3}}
  %cp = u8[100]{0} collective-permute(%v), source_target_pairs={{0,1}}
"""
    c = parse_collectives(hlo)
    assert c["all-gather"]["count"] == 1
    assert c["all-gather"]["bytes"] == 32 * 1024 * 2
    assert c["all-reduce"]["bytes"] == 2 * 256 * 4
    assert c["reduce-scatter"]["bytes"] == 64 * 128 * 4 * 4  # x group size
    assert c["all-to-all"]["bytes"] == 8 * 16 * 2
    assert c["collective-permute"]["bytes"] == 100
    assert c["total_bytes"] == sum(
        c[k]["bytes"] for k in ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))


def test_analytic_flops_vs_6nd():
    """Analytic padded-forward FLOPs ≈ 2·N_active·(1+ε) per token for dense."""
    cfg = ARCHS["deepseek-7b"]
    per_tok = flops_per_token(cfg, 4096, "train")
    floor = 2 * cfg.active_param_count()
    assert per_tok > floor * 0.9
    assert per_tok < floor * 2.5  # attention + padding overhead bounded


def test_analytic_flops_matches_cost_analysis_single_layer():
    """Validate the analytic model against XLA cost_analysis where the
    while-loop undercount cannot bite (1 layer, 1 device, no remat)."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.models import LMModel

    cfg = dataclasses.replace(
        ARCHS["deepseek-7b"], n_layers=1, vocab=1024, tp=1,
        n_heads=8, n_kv_heads=8, head_dim=64, d_model=512, d_ff=1024)
    m = LMModel(cfg, param_dtype=jnp.bfloat16)
    B, S = 2, 256
    batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    fwd = jax.jit(lambda p, b: m.forward(p, b, remat=False))
    compiled = fwd.lower(m.abstract_params(), batch).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jax returns [dict] (one per computation)
        ca = ca[0]
    got = float(ca.get("flops", 0))
    want = B * S * flops_per_token(cfg, S, "prefill")
    assert 0.5 < got / want < 2.0, (got, want)


def test_analytic_decode_flops_scale():
    cfg = ARCHS["deepseek-7b"]
    train = analytic_flops(cfg, SHAPES["train_4k"])
    decode = analytic_flops(cfg, SHAPES["decode_32k"])
    assert decode < train / 1000  # one token vs 1M tokens x4 passes


DRYRUN_SMOKE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses
import jax, jax.numpy as jnp
from repro.configs.registry import ARCHS
from repro.configs.base import ShapeSpec, input_specs
from repro.distributed.sharding import set_mesh
from repro.launch import steps as steps_mod
from repro.models import LMModel
from repro.train.optimizer import AdamWConfig

from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
set_mesh(mesh)
cfg = dataclasses.replace(ARCHS["chatglm3-6b"].reduced(), tp=2, n_kv_heads=2, n_heads=4)
shape = ShapeSpec("smoke", 64, 8, "train")
model = LMModel(cfg, param_dtype=jnp.float32)
opt_cfg = AdamWConfig(state_dtype=jnp.float32)
step = steps_mod.make_train_step(model, opt_cfg)
in_sh = (steps_mod.param_shardings(model), steps_mod.opt_state_shardings(model),
         steps_mod.batch_shardings(cfg, shape))
args = (model.abstract_params(), steps_mod.abstract_opt_state(model, opt_cfg),
        input_specs(cfg, shape))
with mesh:
    compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
txt = compiled.as_text()
has_coll = any(op in txt for op in ("all-reduce", "all-gather", "reduce-scatter"))
print(json.dumps({"ok": True, "has_collectives": has_coll}))
"""


@pytest.mark.slow
def test_dryrun_small_mesh_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", DRYRUN_SMOKE], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["has_collectives"]

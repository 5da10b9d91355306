"""``chip_smoke.py`` end to end on the CPU at a tiny size.

The script refuses to run without a TPU; these tests bypass that check from
the test side (they replace ``chip_smoke.require_tpu`` in a child process)
and run every other line of it.  Children keep JAX on the CPU and their
compile cache in a temporary directory.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BYPASS = ("import sys, jax, chip_smoke; "
          "chip_smoke.require_tpu = jax.devices; "
          "sys.exit(chip_smoke.main(sys.argv[1:]))")


def _run(args, tmp_path, code=None, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=ROOT)
    # set, never inherited: a module imported earlier in this process may
    # have forced a device count of its own
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable] + (["-c", code] if code else
                              [os.path.join(ROOT, "chip_smoke.py")]) + args
    return subprocess.run(cmd, env=env, cwd=tmp_path, capture_output=True,
                          text=True, timeout=600)


def test_refuses_to_run_without_a_tpu(tmp_path):
    out = _run(["--n", "4000"], tmp_path)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_one_chip_path_matches_the_oracle_on_cpu(tmp_path):
    out = _run(["--n", "4000"], tmp_path, code=BYPASS)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    assert "epoch 0 -> 1" in out.stdout
    # the compile cache went where JAX_COMPILATION_CACHE_DIR says
    assert os.listdir(tmp_path / "cache")


def test_sharded_path_on_four_cpu_devices(tmp_path):
    # 20k keys: enough that float32 CDF routing once misrouted keys next to
    # a shard boundary
    out = _run(["--chips", "4", "--n", "20000"], tmp_path, code=BYPASS,
               devices=4)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["device"]["count"] == 4
    assert "match the single-chip index and the oracle" in out.stdout


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """Unset, the cache goes to the fixed in-checkout directory; set,
    JAX_COMPILATION_CACHE_DIR wins and no other directory is configured."""
    import jax
    from repro.launch import compile_cache

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if env_dir:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before[0]
        else:
            assert got == compile_cache.DEFAULT_CACHE_DIR
            assert got == os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])

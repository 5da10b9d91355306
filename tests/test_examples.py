"""The public-API examples run end to end at a small size.

Each example drives the ``StringIndex`` facade or the ``IndexService``
request plane the way a user would, so the public surface cannot break
while the unit tests still pass.  Children keep JAX on the CPU and their
temporary files in the test's own directory.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.parametrize("example,args,says", [
    ("quickstart", ["--n", "4000"], "restored lookups identical=True"),
    ("ycsb_demo", ["--n", "2000", "--ops", "400"], "LITS"),
    ("serve_index_service", ["--n", "64"], "OK: coalesced"),
], ids=["quickstart", "ycsb_demo", "serve_index_service"])
def test_example_runs(tmp_path, example, args, says):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "examples", f"{example}.py")]
        + args, env=env, cwd=tmp_path, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert says in out.stdout, out.stdout[-4000:]

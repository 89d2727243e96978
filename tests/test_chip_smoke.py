"""CPU tests for chip_smoke.py's contract: the toy rehearsal runs the
same code path and can never be mistaken for a chip run, and the full
size refuses to run without a TPU.

The script is driven as a child process, as the driver drives it, with
``JAX_PLATFORMS=cpu`` in the child's environment so that no child
reaches for the TPU library."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, **env):
    child_env = dict(os.environ, JAX_PLATFORMS="cpu",
                     JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    child_env.pop("XLA_FLAGS", None)    # one device, as on a one-chip host
    child_env.update(env)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=child_env, capture_output=True, text=True,
        timeout=600)


def test_toy_rehearsal_reports_the_cpu(tmp_path):
    r = _run(["--size", "toy"], tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[0]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    records = [json.loads(ln) for ln in lines[1:-1]]
    assert {"serve", "rowconv", "ops"} <= {r.get("phase") for r in records}
    # the compile cache is where JAX_COMPILATION_CACHE_DIR says: the
    # script sets no other directory
    assert records[0]["compile_cache"] == str(tmp_path / "jax_cache")


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_full_size_refuses_the_cpu(tmp_path, args):
    r = _run(args, tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout

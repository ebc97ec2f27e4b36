"""Process start-up plumbing (opensearch_tpu/bootstrap.py): where the
persistent compile cache goes. Every case runs in a fresh interpreter,
because the answer is a property of a process's environment at its first
touch of JAX."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

_PROBE = (
    "import json, jax;"
    "from opensearch_tpu.bootstrap import configure_compile_cache;"
    "before = jax.config.jax_compilation_cache_dir;"
    "got = configure_compile_cache();"
    "print(json.dumps({'returned': got, 'before': before,"
    " 'config': jax.config.jax_compilation_cache_dir}))"
)


def _probe(cwd: Path, cache_env: str | None) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)})
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=str(cwd), env=env,
        capture_output=True, text=True, timeout=120, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    """JAX reads the variable itself; the helper reports it and sets no
    directory of its own."""
    chosen = str(tmp_path / "chosen-from-outside")
    got = _probe(tmp_path, chosen)
    assert got["before"] == chosen  # JAX's own reading of the variable
    assert got["config"] == chosen and got["returned"] == chosen


def test_default_cache_dir_is_fixed_by_the_checkout(tmp_path):
    """Unset: `<checkout>/.jax_cache`, the same from any working directory
    and in every process — a cache that moves never hits."""
    other = tmp_path / "elsewhere"
    other.mkdir()
    a = _probe(tmp_path, None)
    b = _probe(other, None)
    assert a["before"] is None
    assert a["config"] == a["returned"] == str(REPO / ".jax_cache")
    assert b["config"] == a["config"]

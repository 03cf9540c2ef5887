"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, is the only cache directory;
otherwise the cache is ``<checkout>/.jax_cache`` whatever the current
directory (the path is part of the cache key, so it must not move).
Each case runs in a fresh interpreter started from an unrelated
directory, since the setting is per process.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax
from repro.core import compile_cache
d = compile_cache.ensure_persistent_cache()
print(d)
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("env_dir", [False, True])
def test_persistent_cache_dir(tmp_path, env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if env_dir:
        want = str(tmp_path / "elsewhere")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]
    assert os.path.isdir(want)
    assert not (tmp_path / ".jax_cache").exists()

"""Where the persistent compile cache lives (`bench/common.py`).

`JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and nothing in
code overrides it; when unset, the cache sits at the fixed
`<repo>/.jax_cache`, so a cache path never moves between runs."""

from __future__ import annotations

import os
import subprocess
import sys

import jax

from pmdfc_tpu.bench import common

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_cache_dir_is_honored(tmp_path):
    code = ("import jax; from pmdfc_tpu.bench.common import "
            "enable_compile_cache; enable_compile_cache(); "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    env.pop("PMDFC_COMPILE_CACHE", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                       timeout=120)
    assert p.returncode == 0, p.stderr.decode()[-2000:]
    assert p.stdout.decode().split()[-1] == str(tmp_path)


def test_unset_cache_dir_is_the_fixed_repo_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PMDFC_COMPILE_CACHE", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        common.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

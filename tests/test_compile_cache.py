"""Where entry points put JAX's persistent compilation cache
(``repro.launch.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` when it is
set, else the fixed ``<repo>/.jax_cache`` — never a temporary path."""
from __future__ import annotations

import os

import pytest

from conftest import REPO, run_in_subprocess

_SNIPPET = """
import os
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
if %(env)r:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = %(env)r
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compilation_cache

got = enable_compilation_cache()
print("DIR", got)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("REPO", REPO_CACHE_DIR)
# force a write: only the chosen directory may receive it
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


def _lines(out: str) -> dict[str, str]:
    return dict(ln.split(" ", 1) for ln in out.splitlines()
                if ln.split(" ", 1)[0] in ("DIR", "CONFIG", "REPO"))


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "repo"])
def test_compile_cache_location(env_set, tmp_path):
    env = str(tmp_path / "jc") if env_set else ""
    repo_dir = os.path.join(REPO, ".jax_cache")
    listing = lambda: sorted(os.listdir(repo_dir)) if os.path.isdir(repo_dir) else None
    before = listing()
    out = _lines(run_in_subprocess(_SNIPPET % {"env": env}, n_devices=1))
    assert out["REPO"] == repo_dir
    if env_set:
        assert out["DIR"] == out["CONFIG"] == env
        assert os.listdir(env), "no cache entry written to the variable's dir"
        assert listing() == before, "an entry went to <repo>/.jax_cache"
    else:
        assert out["DIR"] == out["CONFIG"] == repo_dir
        assert listing(), "no cache entry written to <repo>/.jax_cache"

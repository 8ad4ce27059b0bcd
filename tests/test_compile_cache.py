"""The compile-cache rule (mxnet_tpu.runtime.enable_compile_cache): the
environment places the cache if it wants to; otherwise the cache lives at
one fixed path under the checkout, the same for every process."""
import os
import subprocess
import sys

import jax

from mxnet_tpu.runtime import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_placement_is_left_alone(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_one_fixed_path_in_every_process(tmp_path):
    """Two processes started from two different directories agree on
    <checkout>/.jax_cache — nothing from tempfile, a pid or the clock."""
    code = ("from mxnet_tpu.runtime import enable_compile_cache as e; "
            "import jax; p = e(); "
            "assert jax.config.jax_compilation_cache_dir == p; print(p)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=cwd, env=env,
                              stdout=subprocess.PIPE, text=True)
             for cwd in (str(tmp_path), REPO)]
    outs = [p.communicate(timeout=120)[0].strip() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    assert outs == [os.path.join(REPO, ".jax_cache")] * 2

"""Where the entry points keep JAX's persistent compile cache
(``repro.compile_cache``). Each case runs in a child process, so this
process's JAX configuration is never touched."""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = textwrap.dedent("""
    import jax
    from repro import compile_cache
    print(compile_cache.enable())
    print(jax.config.jax_compilation_cache_dir)
""")


def _probe(cache_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_env_dir_wins_and_nothing_is_set_in_code(tmp_path):
    d = str(tmp_path / "jaxcache")
    used, configured = _probe(d)
    assert used == d
    assert configured == d  # what JAX itself read from the variable


def test_unset_env_uses_the_fixed_dir_in_the_checkout():
    used, configured = _probe(None)
    assert used == configured == os.path.join(REPO, ".jax_cache")

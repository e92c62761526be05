"""Where the persistent compilation cache lands: the entry points' helper
honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise uses a fixed
``<checkout>/.jax_cache``; importing the library turns nothing on. Each
case runs in a fresh interpreter, since JAX reads the variable at import."""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, cache_env: str | None) -> str:
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_receives_the_cache(tmp_path):
    code = (
        "import jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n")
    assert _run(code, str(tmp_path)) == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_default_dir_is_fixed_in_the_checkout():
    code = ("import jax\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    assert _run(code, None) == str(REPO / ".jax_cache")


@pytest.mark.parametrize("module", ["repro.svm", "repro.core.cv"])
def test_library_import_sets_no_cache(module):
    code = (f"import jax, {module}\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    assert _run(code, None) == "None"

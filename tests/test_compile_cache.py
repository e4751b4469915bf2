"""The compile-cache placement rule (utils/compile_cache.py): placed
from outside through JAX_COMPILATION_CACHE_DIR when that is set, at
<checkout>/.jax_cache otherwise. Each case needs a fresh interpreter:
jax reads the variable when it is first imported."""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = (
    "import jax\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "from lightgbm_tpu.utils.compile_cache import enable_compile_cache\n"
    "got = enable_compile_cache()\n"
    "assert got == jax.config.jax_compilation_cache_dir\n"
    "print(repr(before), repr(got))\n")


@pytest.mark.parametrize("outside", ["/some/dir", None])
def test_cache_dir_is_placed_from_outside_or_in_the_checkout(outside):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if outside:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         cwd=_REPO, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    before, got = out.stdout.split()
    if outside:
        # jax took the variable itself; the function changed nothing
        assert before == got == repr(outside)
    else:
        assert before == "None"
        assert got == repr(os.path.join(_REPO, ".jax_cache"))

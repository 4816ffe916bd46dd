"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, `repro.launch.serve.main`,
`repro.launch.train.main`, the benchmark ``main()``s) call
`use_compile_cache` once before they compile anything. Library code and
test set-up never call it, so importing the package changes no JAX config.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the path is part of the cache
key, and a directory inside the checkout, because the program writes
nothing outside it. ``.jax_cache`` is listed in ``.gitignore``.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: src/repro/launch/compile_cache.py -> three levels up
CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place and return
    that directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

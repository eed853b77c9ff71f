import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# a compile cache of the tests' own, so that they neither read nor fill the
# benchmark's (``enable_compile_cache`` keeps a directory that is set)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.environ.get("TMPDIR", "/tmp"), "bench-tests-jax-cache"))

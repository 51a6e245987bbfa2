import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

# compile into a cache of the test session's own, never the checkout's
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import atexit
    import shutil
    import tempfile
    _cache = tempfile.mkdtemp(prefix="bench-tests-jax-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache
    atexit.register(shutil.rmtree, _cache, True)

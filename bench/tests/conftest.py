"""The benchmark's own tests: ``python -m pytest bench/tests`` on the CPU.

They run the harness at a tiny size, so JAX is held to the CPU and its
compile cache goes to a temporary directory, never into the checkout.
"""

import os
import shutil
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_CACHE = tempfile.mkdtemp(prefix="bench_tests_jax_cache_")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _CACHE)
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_sessionfinish(session, exitstatus):
    shutil.rmtree(_CACHE, ignore_errors=True)

"""Path and cache set-up shared by the tools (as ``bench/run.py`` does)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".cache",
                                                       "jax")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

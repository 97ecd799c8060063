"""The benchmark's own tests: ``pytest bench/tests`` from the repository
root. They run on the CPU at tiny sizes; none of their numbers is a
device metric."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

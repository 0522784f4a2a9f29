"""The gate's door to `benchmark/tests/test_account.py`: every case keeps a node
of its own here, and the file itself stays the benchmark's."""
from benchmark.tests.test_account import *  # noqa: F401,F403

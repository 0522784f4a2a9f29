"""The gate's door to `benchmark/tests/test_granite_cell.py`: every case keeps a node
of its own here, and the file itself stays the benchmark's."""
from benchmark.tests.test_granite_cell import *  # noqa: F401,F403

"""The benchmark's tests run on the CPU, with the benchmark's modules and
the program importable."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

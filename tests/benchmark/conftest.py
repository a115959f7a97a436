"""Tests of the benchmark's own code (``benchmarks/``): light, on the
CPU, none marked slow. The repo's root goes on ``sys.path`` so that
``benchmarks`` imports as it does under ``benchmarks/run.py``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

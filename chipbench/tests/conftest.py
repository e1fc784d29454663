"""Tests of the benchmark itself; not part of tier 1 (which collects tests/).

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

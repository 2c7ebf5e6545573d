"""Puts the repository root on ``sys.path`` so the tests import
``chipbench`` as the benchmark's command does."""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

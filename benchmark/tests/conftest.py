"""benchmark/tests run by hand on the CPU: python -m pytest benchmark/tests -q
(they are not part of tests/, the repo's own suite)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

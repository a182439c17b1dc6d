"""The benchmark's own self-tests run on the CPU, at toy sizes, and prove
arithmetic and plumbing only — never a time.  ``python -m pytest
benchmark/tests`` from the root of the repo."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

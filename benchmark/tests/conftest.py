"""Tests of the benchmark's own files; run by hand with
``pytest benchmark/tests`` (CPU; the rehearsals compile tiny models, so
tens of seconds each).  Not part of the repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

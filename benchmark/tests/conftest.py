"""Tests of the benchmark's own yardstick. Run by hand, on the CPU:

    python -m pytest benchmark/tests -q

They are not part of the repository's tier-1 tests (`tests/`)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

"""Tests of the benchmark's own yardstick (not part of the repo's tier-1
suite): ``python -m pytest benchmarks/tests -q`` from the repo's root.
The rehearsal tests start real ``serve`` children on the CPU backend and
take about a minute each."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

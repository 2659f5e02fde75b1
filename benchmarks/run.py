#!/usr/bin/env python3
"""The benchmark's command: one run of one cell (see lib/harness.py)."""
import time

_T_START = time.perf_counter()  # set-up is counted from here: imports are part of it

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=_T_START))

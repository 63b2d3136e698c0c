#!/usr/bin/env python3
"""``find_knee.py`` for a cell whose runner is
``runners/serve_sparse_linear.py``: the same sweep under that runner's
``Stack``, ``CHECK`` and counters (``find_knee.py`` builds ``serve.Stack``
by name, which would load the weights as another family's).

    python benchmarks/find_knee_sparse_linear.py <cell> <seconds> <rate> ...

The rates of ``traffic/longctx_12k_512.json`` came from
``serve_sparse_linear_longctx 51 0.7 0.8 0.9 1.0 1.1`` (PERF.md section 4).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import find_knee  # noqa: E402
from benchmarks.runners import serve_sparse_linear  # noqa: E402

if __name__ == "__main__":
    with serve_sparse_linear._names_swapped():
        find_knee.main()

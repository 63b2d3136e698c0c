#!/usr/bin/env python3
"""``find_knee.py`` for a cell whose runner is
``runners/serve_latent_moe_ssm.py``: the same sweep under that runner's
``Stack``, ``CHECK`` and counters (``find_knee.py`` builds ``serve.Stack``
by name, which would load the weights as another family's).

    python benchmarks/find_knee_latent_moe_ssm.py <cell> <seconds> <rate> ...

The rates of ``traffic/agent_1k_768.json`` came from such a sweep (PERF.md
section 4 has its rows).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import find_knee  # noqa: E402
from benchmarks.runners import serve_latent_moe_ssm  # noqa: E402

if __name__ == "__main__":
    with serve_latent_moe_ssm._names_swapped():
        find_knee.main()

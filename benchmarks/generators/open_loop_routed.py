"""``open_loop`` as it stands (the same schedule from the same parameters),
for a model whose tokens are routed to experts: such a cell is run by
``runners/serve_routed.py``, whose comparison with the reference can hold
a router's near-ties; a generator names its runner, so it needs this name.
"""

from benchmarks.generators.open_loop import generate  # noqa: F401

RUNNER = "serve_routed"

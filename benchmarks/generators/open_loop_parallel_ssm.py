"""``open_loop`` as it stands (the same schedule from the same parameters),
for a model that runs a state-space mixer beside attention in every block:
such a cell is run by ``runners/serve_parallel_ssm.py``, whose comparison
with the reference replays the check's sequences through the engine's own
programs, pages and state rows; a generator names its runner, so it needs
this name.
"""

from benchmarks.generators.open_loop import generate  # noqa: F401

RUNNER = "serve_parallel_ssm"

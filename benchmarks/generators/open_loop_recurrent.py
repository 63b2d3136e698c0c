"""``open_loop`` as it stands (the same schedule from the same parameters),
for a model with recurrent layers: such a cell is run by
``runners/serve_recurrent.py``, whose comparison with the reference draws
prompts longer than two chunks of the prefill's chunked recurrence; a
generator names its runner, so it needs this name.
"""

from benchmarks.generators.open_loop import generate  # noqa: F401

RUNNER = "serve_recurrent"

"""``open_loop`` as it stands (the same schedule from the same parameters),
for a model that caches latent rows and routes tokens to experts: such a
cell is run by ``runners/serve_latent.py``, whose comparison with the
reference holds a router's near-ties and reads the rows the engine's own
programs left in its pool; a generator names its runner, so it needs this
name.
"""

from benchmarks.generators.open_loop import generate  # noqa: F401

RUNNER = "serve_latent"

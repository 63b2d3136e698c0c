"""``open_loop`` as it stands (the same schedule from the same parameters),
for a model whose layers are one thing each, a state-space mixer OR
attention OR a routed feed-forward in a latent, a chip's share of its
experts held: such a cell is run by ``runners/serve_latent_moe_ssm.py``,
whose comparison holds the routed layer apart under the reference's routing
before it replays the check's sequences through the engine's own programs,
pages and packed state rows; a generator names its runner, so it needs this
name.
"""

from benchmarks.generators.open_loop import generate  # noqa: F401

RUNNER = "serve_latent_moe_ssm"

"""``open_loop`` as it stands (the same schedule from the same parameters),
for a model of shortcut-connected double layers that holds a chip's share
of its routed experts: such a cell is run by
``runners/serve_shortcut_moe.py``, whose comparison with the reference pins
the router's columns, reads the rows the engine's own programs left in its
two pool layers a layer, and holds the held experts' product apart; a
generator names its runner, so it needs this name.
"""

from benchmarks.generators.open_loop import generate  # noqa: F401

RUNNER = "serve_shortcut_moe"

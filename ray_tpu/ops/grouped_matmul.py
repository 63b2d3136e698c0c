"""Grouped gated MLP for routed experts on TPU: rows sorted by expert, each
expert's three matrices read from HBM at most once a call.

The dropless routed layer (models/moe.py ``routed_mlp``) lays the
(token, expert) assignments out expert after expert, each expert's run
padded to whole row tiles, so that a tile of rows belongs to ONE expert.
The grid walks the tiles; ``tile_expert`` (scalar prefetch) names each
tile's expert, and the expert's ``w_gate`` / ``w_up`` / ``w_down`` blocks
are fetched by that index.  Consecutive tiles of one expert ask for the
same blocks, which the pipeline does not fetch again; an expert no row was
routed to is never asked for.  With few rows an expert (decoding) the call
is bound by streaming the experts' weights, 3 x d_model x d_expert each.

The weights come stacked over layers (``[n_layers, n_experts, ...]``) with
the layer's index as a prefetched scalar, like the page pool of
``paged_attention``: slicing a layer out of the stack for a custom call
would copy it, the whole of what the kernel exists to read once.

An expert whose three matrices do not fit the kernel's VMEM twice over
(models/longcat_flash.py: 3 x 6144 x 2048, 75 MB) is read in COLUMN BLOCKS
of its hidden width (``f_block``): a second, inner grid axis walks them and
the tile's output accumulates in float32 scratch.  Every byte of the expert
is still read once a tile; a tile no row was routed to asks for the block
the last computed tile ended on, so it fetches nothing.

Off the TPU the same kernel runs through the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# double-buffered blocks of one expert (2 x 3 x d_model x d_expert) pass the
# 16 MiB a kernel gets by default at SDAR's widths (18.9 MB); a v5e core has
# 128 MiB of VMEM
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# what an expert's double-buffered blocks may take of it (the rest: the row
# tiles in and out, the float32 products)
WEIGHT_BLOCKS_BYTES = 40 * 1024 * 1024
LANES = 128


def f_block(d: int, f: int, itemsize: int) -> int:
    """Columns of an expert's hidden width a grid step reads: all ``f``
    where its three matrices fit ``WEIGHT_BLOCKS_BYTES`` twice over, else
    the widest whole-lane divisor of ``f`` that does."""
    fits = WEIGHT_BLOCKS_BYTES // (2 * 3 * d * itemsize)
    if f <= fits:
        return f
    for n in range(2, f // LANES + 1):
        if f % n == 0 and (f // n) % LANES == 0 and f // n <= fits:
            return f // n
    raise ValueError(
        f"no whole-lane block of an expert [{d}, {f}] fits "
        f"{WEIGHT_BLOCKS_BYTES} bytes of VMEM twice over")


def _grouped_mlp_kernel(tile_expert_ref, tiles_used_ref, layer_ref, x_ref,
                        wg_ref, wu_ref, wd_ref, o_ref):
    del tile_expert_ref, layer_ref  # read by the index maps

    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        o_ref[...] = jnp.dot(
            h, wd_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _grouped_mlp(x, w_gate, w_up, w_down, tile_expert, tiles_used, layer, *,
                 tile: int, interpret: bool):
    rows, d = x.shape
    f = w_gate.shape[-1]

    def weight(shape):
        return pl.BlockSpec((None, None) + shape,
                            lambda i, te, used, li: (li[0], te[i], 0, 0))

    def rows_of(i, te, used, li):
        return (i, 0)

    return pl.pallas_call(
        _grouped_mlp_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, d), rows_of), weight((d, f)),
                      weight((d, f)), weight((f, d))],
            out_specs=pl.BlockSpec((tile, d), rows_of)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_grouped_mlp",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(tiles_used, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), x, w_gate, w_up, w_down)


def _grouped_mlp_blocks_kernel(tile_expert_ref, tiles_used_ref, layer_ref,
                               x_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref):
    del tile_expert_ref, layer_ref  # read by the index maps
    j = pl.program_id(1)

    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "fb", "interpret"))
def _grouped_mlp_blocks(x, w_gate, w_up, w_down, tile_expert, tiles_used,
                        layer, *, tile: int, fb: int, interpret: bool):
    """``_grouped_mlp`` with an expert read in ``f // fb`` column blocks."""
    rows, d = x.shape
    nj = w_gate.shape[-1] // fb

    def block(i, j, used):  # a tile not computed stays on the last block
        return jnp.where(i < used[0], j, nj - 1)

    def rows_of(i, j, te, used, li):
        return (i, 0)

    return pl.pallas_call(
        _grouped_mlp_blocks_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows // tile, nj),
            in_specs=[
                pl.BlockSpec((tile, d), rows_of),
                pl.BlockSpec((None, None, d, fb), lambda i, j, te, used, li:
                             (li[0], te[i], 0, block(i, j, used))),
                pl.BlockSpec((None, None, d, fb), lambda i, j, te, used, li:
                             (li[0], te[i], 0, block(i, j, used))),
                pl.BlockSpec((None, None, fb, d), lambda i, j, te, used, li:
                             (li[0], te[i], block(i, j, used), 0))],
            out_specs=pl.BlockSpec((tile, d), rows_of),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_grouped_mlp",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(tiles_used, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), x, w_gate, w_up, w_down)


def grouped_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, tile_expert: jax.Array, tiles_used,
                layer, *, tile: int) -> jax.Array:
    """``silu(x @ w_gate[e]) * (x @ w_up[e]) @ w_down[e]`` for every row of
    x, ``e`` being the expert of the row's tile.

    x: [rows, d_model], ``rows`` a multiple of ``tile``.  w_gate / w_up:
    [n_layers, n_experts, d_model, d_expert]; w_down: [n_layers, n_experts,
    d_expert, d_model]; only ``layer`` (int32 scalar, traced or not) is
    read.  tile_expert: [rows // tile] the expert of each tile, the same
    for neighbours that share one.  Tiles from ``tiles_used`` on are not
    computed and their rows of the result are undefined.  Operands go to
    the MXU in x's dtype, products accumulate in float32.
    """
    rows, d = x.shape
    if rows % tile or tile_expert.shape != (rows // tile,):
        raise ValueError(
            f"{rows} rows are not {tile_expert.shape[0]} tiles of {tile}")
    if (w_gate.ndim != 4 or w_gate.shape != w_up.shape
            or w_down.shape != w_gate.shape[:2] + w_gate.shape[:1:-1]
            or w_gate.shape[2] != d):
        raise ValueError(
            f"grouped_mlp takes w_gate and w_up [layers, experts, {d}, f] "
            f"and w_down [layers, experts, f, {d}]; got {w_gate.shape}, "
            f"{w_up.shape}, {w_down.shape}")
    fb = f_block(d, w_gate.shape[-1], x.dtype.itemsize)
    blocks = {} if fb == w_gate.shape[-1] else {"fb": fb}
    return (_grouped_mlp_blocks if blocks else _grouped_mlp)(
        x, w_gate.astype(x.dtype), w_up.astype(x.dtype),
        w_down.astype(x.dtype), tile_expert, tiles_used, layer, tile=tile,
        interpret=jax.default_backend() != "tpu", **blocks)

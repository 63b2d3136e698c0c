"""Grouped MLP for routed experts on TPU: rows sorted by expert, each
expert's matrices read from HBM at most once a call.  An expert is one of
two FORMS, told by whether it has a gate matrix: the SiLU-gated three
(``silu(x W_gate) * (x W_up) W_down``: Mixtral, Qwen3-MoE, DeepSeek) or two
matrices around a squared ReLU (``relu(x W_up)^2 W_down``:
models/nemotron_h.py's latent experts).  One kernel body for both; the
form is static, and a gated caller lowers to the text it had.

The dropless routed layer (models/moe.py ``routed_mlp``) lays the
(token, expert) assignments out expert after expert, each expert's run
padded to whole row tiles, so that a tile of rows belongs to ONE expert.
The grid walks the tiles; ``tile_expert`` (scalar prefetch) names each
tile's expert, and the expert's ``w_gate`` / ``w_up`` / ``w_down`` blocks
are fetched by that index.  Consecutive tiles of one expert ask for the
same blocks, which the pipeline does not fetch again; an expert no row was
routed to is never asked for.  With few rows an expert (decoding) the call
is bound by streaming the experts' weights, 3 (or 2) x d_model x d_expert
each.

The weights come stacked over layers (``[n_layers, n_experts, ...]``) with
the layer's index as a prefetched scalar, like the page pool of
``paged_attention``: slicing a layer out of the stack for a custom call
would copy it, the whole of what the kernel exists to read once.

An expert whose matrices do not fit the kernel's VMEM twice over
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


def f_block(d: int, f: int, itemsize: int, matrices: int = 3) -> int:
    """Columns of an expert's hidden width a grid step reads: all ``f``
    where its ``matrices`` fit ``WEIGHT_BLOCKS_BYTES`` twice over, else
    the widest whole-lane divisor of ``f`` that does."""
    fits = WEIGHT_BLOCKS_BYTES // (2 * matrices * d * itemsize)
    if f <= fits:
        return f
    for n in range(2, f // LANES + 1):
        if f % n == 0 and (f // n) % LANES == 0 and f // n <= fits:
            return f // n
    raise ValueError(
        f"no whole-lane block of an expert [{d}, {f}] fits "
        f"{WEIGHT_BLOCKS_BYTES} bytes of VMEM twice over")


def _hidden(x, w_in):
    """What an expert's last matrix multiplies, of a tile of rows x: by the
    matrices before it, ``silu(x W_gate) * (x W_up)`` of two or
    ``relu(x W_up)^2`` of one."""
    first = jnp.dot(x, w_in[0][...], preferred_element_type=jnp.float32)
    if len(w_in) == 1:
        up = jnp.maximum(first, 0.0)
        return (up * up).astype(x.dtype)
    up = jnp.dot(x, w_in[1][...], preferred_element_type=jnp.float32)
    return (first * jax.nn.sigmoid(first) * up).astype(x.dtype)


def _grouped_mlp_kernel(tile_expert_ref, tiles_used_ref, layer_ref, x_ref,
                        *refs):
    del tile_expert_ref, layer_ref  # read by the index maps
    *w_in, wd_ref, o_ref = refs

    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        o_ref[...] = jnp.dot(
            _hidden(x_ref[...], w_in), wd_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _grouped_mlp(x, w_in, w_down, tile_expert, tiles_used, layer, *,
                 tile: int, interpret: bool):
    rows, d = x.shape
    f = w_down.shape[-2]

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
            in_specs=[pl.BlockSpec((tile, d), rows_of),
                      *(weight((d, f)) for _ in w_in), weight((f, d))],
            out_specs=pl.BlockSpec((tile, d), rows_of)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_grouped_mlp",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(tiles_used, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), x, *w_in, w_down)


def _grouped_mlp_blocks_kernel(tile_expert_ref, tiles_used_ref, layer_ref,
                               x_ref, *refs):
    del tile_expert_ref, layer_ref  # read by the index maps
    *w_in, wd_ref, o_ref, acc_ref = refs
    j = pl.program_id(1)

    @pl.when(pl.program_id(0) < tiles_used_ref[0])
    def _():
        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        h = _hidden(x_ref[...], w_in)
        acc_ref[...] += jnp.dot(h, wd_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "fb", "interpret"))
def _grouped_mlp_blocks(x, w_in, w_down, tile_expert, tiles_used, layer, *,
                        tile: int, fb: int, interpret: bool):
    """``_grouped_mlp`` with an expert read in ``f // fb`` column blocks."""
    rows, d = x.shape
    nj = w_down.shape[-2] // fb

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
                *(pl.BlockSpec((None, None, d, fb),
                               lambda i, j, te, used, li:
                               (li[0], te[i], 0, block(i, j, used)))
                  for _ in w_in),
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
      jnp.asarray(layer, jnp.int32).reshape(1), x, *w_in, w_down)


def grouped_mlp(x: jax.Array, w_gate, w_up: jax.Array,
                w_down: jax.Array, tile_expert: jax.Array, tiles_used,
                layer, *, tile: int) -> jax.Array:
    """``silu(x @ w_gate[e]) * (x @ w_up[e]) @ w_down[e]`` for every row of
    x, ``e`` being the expert of the row's tile; with ``w_gate`` None the
    two-matrix form, ``relu(x @ w_up[e])^2 @ w_down[e]``.

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
    w_in = (w_up,) if w_gate is None else (w_gate, w_up)
    if (w_up.ndim != 4 or w_in[0].shape != w_up.shape
            or w_down.shape != w_up.shape[:2] + w_up.shape[:1:-1]
            or w_up.shape[2] != d):
        raise ValueError(
            f"grouped_mlp takes w_gate (or None) and w_up [layers, experts, "
            f"{d}, f] and w_down [layers, experts, f, {d}]; got "
            f"{[w.shape for w in w_in]}, {w_down.shape}")
    fb = f_block(d, w_up.shape[-1], x.dtype.itemsize, len(w_in) + 1)
    blocks = {} if fb == w_up.shape[-1] else {"fb": fb}
    return (_grouped_mlp_blocks if blocks else _grouped_mlp)(
        x, tuple(w.astype(x.dtype) for w in w_in), w_down.astype(x.dtype),
        tile_expert, tiles_used, layer, tile=tile,
        interpret=jax.default_backend() != "tpu", **blocks)

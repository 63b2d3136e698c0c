"""``ops/lightning.py``'s chunked scan as ONE kernel (interpreted here):
against the definition (``recurrent``) and against the plain chunked form it
replaced (``chunked_plain``), for the three ways its callers hand it heads:
a head its own key (MiniCPM-SALA), keys a group (Falcon-H1), and narrow
heads PACKED side by side in the state's lanes (Nemotron-3-Super)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import lightning

# name -> (heads, key groups, d_k, d_v, heads a state row)
WAYS = {"own_key": (4, 4, 16, 16, 1),
        "grouped": (8, 2, 32, 16, 1),
        "packed": (8, 2, 32, 16, 2),
        "packed_four_across_two_keys": (16, 2, 16, 8, 4),
        # eight heads a key, a head a row (the interpreter takes rows
        # narrower than the lanes as they are; the chip's compiler gets
        # them packed, tests/test_tpu_compile.py)
        "eight_heads_a_key": (16, 2, 16, 16, 1)}


def _draw(way, L, seed=0, dtype=jnp.float32):
    """Decays a token a head, head 0 decaying by exp(-1.6) a token (a
    chunk's running product underflows float32), and a NONZERO S0 laid out
    as the way holds it."""
    H, G, dk, dv, pack = WAYS[way]
    r = np.random.default_rng(seed)
    q, k = (jnp.asarray(r.standard_normal((L, G, dk)), dtype)
            for _ in range(2))
    v = jnp.asarray(r.standard_normal((L, H, dv)), dtype)
    g = -jnp.asarray(r.uniform(1e-3, 0.2, (L, H)), jnp.float32)
    g = g.at[:, 0].set(-1.6)
    S0 = jnp.asarray(r.standard_normal((H, dk, dv)), jnp.float32)
    return q, k, v, g, lightning.pack_state(S0, pack)


def _unpacked(way, S):
    return lightning.unpack_state(S, WAYS[way][-1])


@pytest.mark.parametrize("length", [1, 128, 300])
@pytest.mark.parametrize("way", sorted(WAYS))
def test_the_kernel_equals_the_recurrence_and_the_plain_form(way, length):
    q, k, v, g, S0 = _draw(way, length, seed=length)
    o, S = lightning.chunked(q, k, v, g, S0)
    assert o.shape == v.shape and o.dtype == jnp.float32
    assert S.shape == S0.shape and S.dtype == jnp.float32  # as S0 came
    want_o, want_S = lightning.recurrent(q, k, v, g, _unpacked(way, S0))
    np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(_unpacked(way, S), want_S, atol=2e-5,
                               rtol=1e-5)
    plain_o, plain_S = lightning.chunked_plain(q, k, v, g, S0)
    assert plain_S.shape == S0.shape
    np.testing.assert_allclose(o, plain_o, atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(S, plain_S, atol=1e-5, rtol=1e-5)
    assert bool(jnp.all(jnp.isfinite(o)))  # the strongly decaying head too


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("way", sorted(WAYS))
def test_any_chunk_is_the_same_mathematics(way, chunk):
    q, k, v, g, S0 = _draw(way, 100, seed=chunk)
    want_o, want_S = lightning.chunked_plain(q, k, v, g, S0)
    o, S = lightning.chunked(q, k, v, g, S0, chunk=chunk)
    np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("way", sorted(WAYS))
def test_padded_positions_inside_the_last_chunk_change_nothing(way):
    """g = 0 and a zero key from the middle of the last chunk on: the state
    stays, whatever v and q hold there (a bucket's padded tail)."""
    q, k, v, g, S0 = _draw(way, 200, seed=3)  # the last chunk is 128-255
    real = (jnp.arange(200) < 170)[:, None]
    o, S = lightning.chunked(q, jnp.where(real[..., None], k, 0), v,
                             jnp.where(real, g, 0.0), S0)
    want_o, want_S = lightning.chunked(q[:170], k[:170], v[:170], g[:170],
                                       S0)
    np.testing.assert_allclose(S, want_S, atol=1e-6)
    np.testing.assert_allclose(o[:170], want_o, atol=1e-6)


@pytest.mark.parametrize("way", sorted(WAYS))
def test_a_sequence_in_pieces_of_256_is_the_whole(way):
    """What ``benchmarks/families/minicpm_sala.plant_state_bf16`` does to
    the name (without its rounding): slices of 256 tokens, each from the
    state the one before left, laid out as it was handed back."""
    q, k, v, g, S0 = _draw(way, 600, seed=5)
    want_o, want_S = lightning.chunked(q, k, v, g, S0)
    S, outs = S0, []
    for at in range(0, 600, 256):
        o, S = lightning.chunked(*(x[at:at + 256] for x in (q, k, v, g)), S)
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs), want_o, atol=2e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("way", ["own_key", "packed"])
def test_bfloat16_inputs_are_taken_as_they_come(way):
    """MiniCPM-SALA's q, k and v arrive in bfloat16: the kernel widens a
    block where it reads it, as the plain form widens the arrays."""
    q, k, v, g, S0 = _draw(way, 100, seed=9, dtype=jnp.bfloat16)
    want_o, want_S = lightning.chunked_plain(q, k, v, g, S0)
    o, S = lightning.chunked(q, k, v, g, S0)
    np.testing.assert_allclose(o, want_o, atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("rows", [1, 2, 4, 8])
def test_any_rows_a_block_give_the_same_results(rows):
    """``_rows_a_block`` chooses for VMEM, not for the result: rows of one
    key (1, 2, 4) and both keys' rows (8) a grid step."""
    q, k, v, g, S0 = _draw("grouped", 100, seed=11)
    want_o, want_S = lightning.chunked_plain(q, k, v, g, S0)
    o, S = lightning._scan(q, k, v, g, S0, chunk=32, rows=rows,
                           interpret=True)
    np.testing.assert_allclose(o, want_o, atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(S, want_S, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape,rows", [
    # (state rows, keys, d_k, lanes, chunk): the three served shapes
    ((64, 8, 128, 128, 128), 8),   # Nemotron-3-Super: a key's rows
    ((32, 32, 128, 128, 128), 8),  # MiniCPM-SALA: eight heads and keys
    ((32, 2, 256, 128, 128), 8),   # Falcon-H1: half a key's rows
    ((16, 16, 4096, 1024, 128), 8),  # nothing fits: the fewest all the same
    ((4, 2, 16, 16, 128), 4),      # under a tile of heads: all of them
])
def test_rows_a_block_are_whole_keys_or_parts_of_one_within_the_budget(
        shape, rows):
    n_rows, keys, dk, lanes, chunk = shape
    assert lightning._rows_a_block(*shape) == rows
    share = n_rows // keys
    assert n_rows % rows == 0 and (share % rows == 0 or rows % share == 0)
    assert rows % 8 == 0 or rows == n_rows


def test_the_scan_refuses_what_it_cannot_lay_out():
    q, k, v, g, S0 = _draw("grouped", 4)
    with pytest.raises(ValueError, match="no whole number of heads"):
        lightning.chunked(q[:, :1].repeat(3, 1), k[:, :1].repeat(3, 1), v, g,
                          S0)
    with pytest.raises(ValueError, match="hold no 8 heads"):
        lightning.chunked(q, k, v, g, S0[:, :, :8])
    # heads of different keys side by side in a row
    q, k, v, g, S0 = _draw("own_key", 4)
    with pytest.raises(ValueError, match="different keys"):
        lightning.chunked(q, k, v, g, lightning.pack_state(S0, 2))


def test_under_jit_and_vmap_the_kernel_is_what_it_is_alone():
    """A family's plain forward maps a batch over it (models/falcon_h1.py
    ``apply``)."""
    q, k, v, g, S0 = _draw("grouped", 70, seed=13)
    both = lambda x: jnp.stack([x, x[::-1]])  # noqa: E731
    o, S = jax.jit(jax.vmap(lightning.chunked, in_axes=(0, 0, 0, 0, None)))(
        both(q), both(k), both(v), both(g), S0)
    for i, rev in enumerate((slice(None), slice(None, None, -1))):
        want_o, want_S = lightning.chunked(q[rev], k[rev], v[rev], g[rev], S0)
        np.testing.assert_allclose(o[i], want_o, atol=1e-6)
        np.testing.assert_allclose(S[i], want_S, atol=1e-6)

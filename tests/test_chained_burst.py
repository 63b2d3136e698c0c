"""A greedy burst's chained step against its steps one by one, for every
family ``tests/test_model_parts.py`` builds (its builders, tiny sizes, the
CPU).  In a file of its own: that one already fills a worker's queue under
``--dist loadfile``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import model as lm
from ray_tpu.llm.engine import _burst_counts, _by_name
from test_model_parts import DECODE
from test_model_parts import _chained as chained_program


@pytest.mark.parametrize("burst", [8, 1])
@pytest.mark.parametrize("name", sorted(DECODE))
def test_a_chained_burst_is_its_steps_one_by_one(name, burst):
    """``burst`` chained steps (no host between them) against as many
    ``decode_step_greedy`` calls at ``positions + j`` and a host's sum:
    token for token, count for count, and the pools and state rows they
    leave; a burst of 1 goes through the same eight-row ``acc``, whose
    other rows nothing writes."""
    step, (params, toks, ck, cv, tables, positions, _), cfg, *rows = \
        DECODE[name]()
    rows = dict(*rows)
    active = jnp.asarray([True, True, False, True])
    B = toks.shape[0]
    want_tokens, want_counts = [], {}
    for j in range(burst):
        toks, counted, ck, cv, state = step(
            params, toks, ck, cv, tables, positions + j, active, cfg, **rows)
        rows = {"state": state} if rows else {}
        want_tokens.append(np.asarray(toks))
        for k, n in _by_name(jax.device_get(counted)).items():
            want_counts[k] = want_counts.get(k, 0) + n
    want = (ck, cv, rows)

    chained, (_, toks, ck, cv, tables, positions, _, row, acc), cfg, *rows \
        = chained_program(DECODE[name])
    rows = dict(*rows)
    layout = lm.counted_layout(params, toks, ck, cv, tables, positions,
                               active, cfg, **rows)
    assert acc.shape == (8, B + sum(n for _, n in layout))
    pos = positions
    for j in range(burst):
        (toks, pos, row, acc), none, ck, cv, state = chained(
            params, toks, ck, cv, tables, pos, active, row, acc, cfg, **rows)
        rows = {"state": state} if rows else {}
        assert none == {}
    acc = np.asarray(acc)
    np.testing.assert_array_equal(acc[:burst, :B], np.stack(want_tokens))
    assert (acc[burst:] == -1).all()
    np.testing.assert_array_equal(np.asarray(toks), want_tokens[-1])
    np.testing.assert_array_equal(np.asarray(pos), np.full(B, 5 + burst))
    assert int(row) == burst
    assert _burst_counts(layout, acc[:burst, B:].sum(axis=0)) == want_counts
    assert bool(want_counts) == bool(layout)
    for a, b in zip(jax.tree.leaves((ck, cv, rows)), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

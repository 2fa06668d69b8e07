"""The mailbox-insertion law, staging by rank: two forms, one result.

Since PR 36 the staging has two forms, chosen by the call's shapes
(``_stages_dense``: the lanes at least ``_DENSE_STAGE_RATIO`` of the
nodes): a scatter a field, and one sort by staged index with rank 0
expanded on the node lanes and the tail scattered declared sorted. At
the widths of the matrix (tests/test_insert_oracle_adaptive.py) nearly
every case takes the second; ``parent_stage_by_rank``
(tests/insertion_laws.py) is the first, kept as the reference, and one
call of ``_stage_by_rank`` on built lanes is held to it word for word
in both forms (skewed destinations, more than K at one node, invalid
lanes, a tail over half the lanes, a slice clamped at the lanes' end,
lanes under the threshold). A ladder whose first rung is under the
threshold and whose others are over it is a case of
tests/test_insert_slot_law.py; the dense form's tail from
``_TAIL_LADDER_LANES`` lanes on is tests/test_stage_tail_law.py.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from insertion_laws import (_WAVE_LINK, _burst, _staging_lanes,
                            parent_stage_by_rank)
from timewarp_tpu.interp.jax_engine.common import I32MAX, group_rank
from timewarp_tpu.interp.jax_engine.engine import (_DENSE_STAGE_RATIO,
                                                   JaxEngine)


#: case -> whether the dense form takes it, and its tail at full width
STAGINGS = {
    "uniform": (True, False), "skewed": (True, False),
    "more-than-K-at-one-node": (True, False),
    "invalid-lanes": (True, False), "wide-tail": (True, True),
    "rung-of-four-lanes-a-node": (True, True),
    "clamped-slice": (True, False), "nothing-valid": (True, False),
    "under-the-threshold": (False, False),
    "just-under-the-threshold": (False, False),
    "at-the-threshold": (True, False),
}


@functools.lru_cache(maxsize=None)
def _staging_engine(n, K, P, inbox_src):
    import dataclasses
    sc = dataclasses.replace(_burst(n, K), payload_width=P,
                             inbox_src=inbox_src)
    return JaxEngine(sc, _WAVE_LINK, window="auto", lint="off")


@pytest.mark.parametrize("inbox_src", [False, True], ids=["nosrc", "src"])
@pytest.mark.parametrize("P", [1, 2], ids="P{}".format)
@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("case", list(STAGINGS))
def test_one_staging_equals_the_scatters(case, n, P, inbox_src):
    """``_stage_by_rank`` on built lanes, in whichever form the lane
    count selects, against a scatter a field: every staged buffer word
    for word, ``over`` the same number, and ``wide`` what the lanes
    say (the arrivals of rank 1 and over against half the lanes)."""
    K = 24
    eng = _staging_engine(n, K, P, inbox_src)
    lanes = _staging_lanes(case, n, K, P)
    sd, ok = lanes[0], lanes[1]
    dense, wide = STAGINGS[case]
    assert eng._stages_dense(len(sd)) == dense
    rank = np.asarray(group_rank(jnp.asarray(sd)))
    fits = ok & (rank < K)
    tail = int((fits & (rank > 0)).sum())
    assert (tail > len(sd) // 2) == wide
    *got, got_wide = jax.jit(eng._stage_by_rank)(*lanes)
    want = jax.jit(functools.partial(parent_stage_by_rank, eng))(*lanes)
    assert int(got_wide) == (dense and wide)
    assert (got[1] is None) == (not inbox_src) and len(got[2]) == P
    for name, x, y in zip(("rel", "src", "pay", "over"), got, want):
        for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            assert np.array_equal(a, b), (case, name)
    assert int(got[3]) == int((ok & (rank >= K)).sum())
    assert (int(got[3]) > 0) == (
        case in ("more-than-K-at-one-node", "skewed"))
    assert int((np.asarray(got[0]) != I32MAX).sum()) == int(fits.sum())


def _staging_text(fn, n, L, P):
    lane = jax.ShapeDtypeStruct((L,), np.int32)
    return jax.jit(fn).lower(
        lane, jax.ShapeDtypeStruct((L,), bool), lane, lane,
        (lane,) * P).as_text()


def test_the_dense_form_declares_every_scatter_sorted():
    """One sort, the program's own, and every scatter after it with
    ``indices_are_sorted`` and ``unique_indices``: the flags are what
    keep the compiler from sorting ``(indices, updates)`` again in
    front of each scatter (docs/engines.md "Random delivery")."""
    import re
    n, P = 1024, 2
    eng = _staging_engine(n, 24, P, True)
    text = _staging_text(eng._stage_by_rank, n, 2 * n, P)
    assert len(re.findall(r"stablehlo\.sort", text)) == 1
    scatters = re.findall(r'"stablehlo\.scatter".*?<\{(.*?)\}>', text,
                          flags=re.S)
    # a field a branch of the tail's conditional
    assert len(scatters) == 2 * (2 + P)
    for attrs in scatters:
        assert "indices_are_sorted = true" in attrs, attrs
        assert "unique_indices = true" in attrs, attrs
    assert "stablehlo.gather" not in text


def test_under_the_threshold_staging_lowers_to_the_parents_text():
    """Few lanes for the nodes: the scatters, as the parent lowered
    them, operation for operation."""
    n, P = 1024, 2
    eng = _staging_engine(n, 24, P, True)
    few = math.ceil(_DENSE_STAGE_RATIO * n) // 2
    assert not eng._stages_dense(few)
    text = _staging_text(lambda *a: eng._stage_by_rank(*a)[:4], n, few, P)
    assert text == _staging_text(
        lambda *a: parent_stage_by_rank(eng, *a), n, few, P)
    assert "stablehlo.sort" not in text

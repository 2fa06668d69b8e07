"""The mailbox-insertion law, the slot itself, on built lanes: one call
of ``_insert_sorted`` on lanes built to hold every case of the overflow
(ranks past K at one node, fewer holes than arrivals, none;
tests/insertion_laws.py ``_lanes``) against the parent's form
(``parent_insert_sorted``: the hole words gathered onto the message
lanes, the rank-th set bit as the slot). Whole runs replayed the
parent's way are tests/test_insert_slot_law.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from insertion_laws import _WAVE_LINK, _burst, _lanes, parent_insert_sorted
from timewarp_tpu.interp.jax_engine.engine import JaxEngine


@pytest.mark.parametrize("inbox_src", [False, True], ids=["nosrc", "src"])
@pytest.mark.parametrize("P", [1, 2], ids="P{}".format)
@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("K", [24, 40], ids=["one-word", "two-words"])
def test_one_insertion_equals_the_parents(K, n, P, inbox_src):
    """``_insert_sorted`` on built lanes against the parent's form:
    the three planes bit-equal and ``overflow`` the same number, which
    here is known: the arrivals past each node's holes."""
    import dataclasses
    from timewarp_tpu.ops.numeric import free_bits
    sc = dataclasses.replace(_burst(n, K), payload_width=P,
                             inbox_src=inbox_src)
    eng = JaxEngine(sc, _WAVE_LINK, window="auto", lint="off")
    *lanes, keep = _lanes(n, K, P, 2 * n, seed=K + n + P)
    sd = lanes[3]
    arrivals = np.bincount(sd[sd < n], minlength=n)
    lost = np.maximum(arrivals - (~keep).sum(axis=0), 0)
    assert lost[1] >= 5 and lost[2] == 3 and lost[3] == 3 and lost[4] == 0

    def both(*lanes):
        holes = free_bits(jnp.asarray(keep))
        return (eng._insert_sorted(*lanes, holes, None),
                parent_insert_sorted(eng, *lanes, holes, None))
    got, want = jax.jit(both)(*lanes)
    for name, x, y in zip(("mb_rel", "mb_src", "mb_payload", "overflow"),
                          got, want):
        assert np.array_equal(x, y), name
    assert int(got[3]) == lost.sum() > 0
    assert (np.asarray(got[1]) == lanes[1]).all() == (not inbox_src)

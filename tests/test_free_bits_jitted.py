"""``fill_holes`` past one hole word as the engines run it, under
``jax.jit``: the law of tests/test_free_bits.py (tests/free_bits_laws.py
has its body) at the least K of each word count. A file of its own
because its cost is XLA:CPU's compile of one fusion over K rows: 4 s at
K 40, 31 s at 97, 71 s at 129 alone, two to three times that beside
five busy workers."""

import pytest

from free_bits_laws import fill_holes_law, fill_jitted

#: the least rows with two hole words, four, five: one compile each,
#: whatever the fill; the last too long for tier-1
JITTED_KS = [pytest.param(40, id="K40"), pytest.param(97, id="K97"),
             pytest.param(129, id="K129", marks=pytest.mark.slow)]


@pytest.mark.parametrize("fill", ["all_free", "none_free", "random"])
@pytest.mark.parametrize("K", JITTED_KS)
def test_fill_holes_equals_the_loop(K, fill):
    """The same law on the program the engines run, ``fill_holes``
    under ``jax.jit``, at the least K of each word count past one."""
    fill_holes_law(fill_jitted, K, fill)

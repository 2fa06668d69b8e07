"""The mailbox-insertion law, the slot itself, on the one ladder of
tier-1 whose rungs stage in both forms (tests/test_insert_slot_law.py
has the law, tests/insertion_laws.py ``SLOT_CASES`` the case): 20 000
nodes, the first rung under ``_DENSE_STAGE_RATIO`` and staged by
scatters, the rungs of 8192 senders and over past
``_TAIL_LADDER_LANES`` with the constants as they ship."""

import pytest

from insertion_laws import every_slot_is_the_one_the_parent_gave


@pytest.mark.parametrize("case", ["ladder-of-both-stagings"])
def test_every_slot_is_the_one_the_parent_gave(case):
    every_slot_is_the_one_the_parent_gave(case)

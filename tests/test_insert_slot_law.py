"""The mailbox-insertion law, the slot itself: which slot a message
takes, which no observer could tell apart if it differed.

Runs on the ladder, on the eager path and as a fleet are replayed by an
engine that inserts the parent's way (the hole words gathered onto the
message lanes, the rank-th set bit as the slot:
tests/insertion_laws.py ``ParentInsert``), every leaf of the state
compared bit for bit, the stale words in holes included
(``SLOT_CASES`` and the case's body are in tests/insertion_laws.py too).
The ladder of 20 000 nodes that stages in both forms, two thirds of a
minute of compiling on its own, is
tests/test_insert_slot_both_stagings.py; one call of the insertion on
built lanes against the same copy is tests/test_insert_slot_on_lanes.py.
"""

import pytest

from insertion_laws import SLOT_CASES, every_slot_is_the_one_the_parent_gave

_APART = "ladder-of-both-stagings"


@pytest.mark.parametrize("case", [c for c in SLOT_CASES if c != _APART])
def test_every_slot_is_the_one_the_parent_gave(case):
    every_slot_is_the_one_the_parent_gave(case)

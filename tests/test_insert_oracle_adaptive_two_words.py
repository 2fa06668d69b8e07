"""The mailbox-insertion law, the matrix at the adaptive call site, the
inbox of two hole words (tests/test_insert_oracle_adaptive.py has the
site's other inboxes, tests/insertion_laws.py the matrix and the case's
body).
"""

import pytest

from insertion_laws import insertion_equals_oracle


@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("mailbox", ["fits", "overflows"])
@pytest.mark.parametrize("inbox", ["commutative-two-words"])
@pytest.mark.parametrize("site", ["adaptive"])
def test_insertion_equals_oracle(site, inbox, mailbox, n):
    insertion_equals_oracle(site, inbox, mailbox, n)

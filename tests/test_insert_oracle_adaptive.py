"""The mailbox-insertion law, the matrix at the adaptive call site:
``JaxEngine`` against ``SuperstepOracle`` (tests/insertion_laws.py has
the view both are held to, the matrix and the case's body). A case is
an engine of its own, and a file is one worker's: the other call
site is tests/test_insert_oracle_eager.py, and each site's inbox
of two hole words, the costliest to compile, is its ``_two_words.py``.
"""

import pytest

from insertion_laws import insertion_equals_oracle


@pytest.mark.parametrize("n", [1024, 1000], ids="n{}".format)
@pytest.mark.parametrize("mailbox", ["fits", "overflows"])
@pytest.mark.parametrize("inbox", ["commutative", "ordered"])
@pytest.mark.parametrize("site", ["adaptive"])
def test_insertion_equals_oracle(site, inbox, mailbox, n):
    insertion_equals_oracle(site, inbox, mailbox, n)

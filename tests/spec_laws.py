"""What tests/test_zzzzzzspec.py and tests/test_spec_fleets.py share.
No test lives here."""

from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import ParetoDelay, Quantize


N = 96
BUDGET = 3000


def _sc():
    return gossip(N, fanout=4, burst=True, end_us=300_000,
                  mailbox_cap=16, think_us=700)


def _tail_link():
    """The long-tail link: samples supported on [4000, inf) µs, the
    DECLARED floor the 500 µs quantize grid — the provable-floor /
    practical-floor gap speculation closes."""
    return Quantize(ParetoDelay(4_000, 1.2), 500)

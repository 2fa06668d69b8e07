"""What the files of the flight recorder's tests share
(tests/test_zzzzzflight.py, tests/test_flight_queries.py,
tests/test_flight_bisect.py): the scenarios and the CLI runner. No test
lives here."""

from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring
from timewarp_tpu.net.delays import FixedDelay, Quantize, UniformDelay


N = 32
STEPS = 25


def _gossip():
    sc = gossip(N, fanout=3, burst=True, end_us=150_000,
                mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def _ring():
    sc = token_ring(16, n_tokens=4, think_us=2000,
                    bootstrap_us=1000, end_us=120_000,
                    with_observer=False, mailbox_cap=8)
    return sc, FixedDelay(500)


def _steady_faulted():
    """The worked causal-chain scenario (README, CI): steady gossip
    under a crash + a degraded-link window + a partition — deliveries
    into node 3 after the crash window carry the full chain."""
    from timewarp_tpu.faults.schedule import parse_faults
    sc = gossip(16, fanout=3, steady=True, end_us=300_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3000, 9000), 1000)
    faults = parse_faults("crash:3:50000:120000;"
                          "degrade:all:3:0:300000:2.0:500;"
                          "partition:0-7|8-15:20000:40000")
    return sc, link, faults


def _run_cli(argv):
    from timewarp_tpu.cli import main
    return main(argv)

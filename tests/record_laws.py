"""What tests/test_zzzzzzzzzzzzzzzrecord.py and
tests/test_record_staging_counts.py share. No test lives here."""

from timewarp_tpu.interp.jax_engine.batched import BatchSpec
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay


N = 2048

FLEET = BatchSpec(seeds=(0, 1))


def _steady(n=N):
    """Steady gossip: the active set doubles a round, so a run crosses
    the ladder's rungs on its ramp (tests/test_zzzzzzzzzzzzzfleet_rung)."""
    sc = gossip(n, fanout=1, think_us=1_000, gossip_interval=1_000,
                end_us=60_000, steady=True, mailbox_cap=8)
    return sc, Quantize(UniformDelay(500, 4_500), 1_000)

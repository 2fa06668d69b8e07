"""What tests/test_zzzzintegrity.py and tests/test_integrity_recovery.py
share. No test lives here."""

from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.net.delays import Quantize, UniformDelay


N = 40
BUDGET = 50
CHUNK = 8


def _gossip():
    sc = gossip(N, fanout=3, burst=True, end_us=150_000,
                mailbox_cap=16)
    return sc, Quantize(UniformDelay(3000, 9000), 1000)


def _pack():
    from timewarp_tpu.sweep.spec import SweepPack
    return SweepPack.from_json([
        {"id": "r0", "scenario": "token-ring",
         "params": {"nodes": 16, "n_tokens": 2, "think_us": 2000,
                    "end_us": 60000, "mailbox_cap": 8},
         "link": "uniform:1000:5000", "seed": 0, "budget": 40},
        {"id": "g0", "scenario": "gossip",
         "params": {"nodes": 24, "fanout": 3, "burst": True,
                    "end_us": 100000, "mailbox_cap": 16},
         "link": "quantize:1000:uniform:3000:9000", "seed": 1,
         "window": "auto", "budget": 50},
    ])

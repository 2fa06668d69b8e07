"""What the files of the adaptive dispatch's laws share
(tests/test_zzzdispatch.py, tests/test_dispatch_replay_law.py,
tests/test_dispatch_fleets.py): the wave, the schedules and the two
engines of a law, controller-driven and replaying. No test lives
here."""

from timewarp_tpu.dispatch import DecisionTrace, DispatchController
from timewarp_tpu.faults.schedule import FaultSchedule, LinkWindow
from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.models.gossip import gossip, gossip_links
from timewarp_tpu.net.delays import Quantize


BUDGET = 1 << 14


def _wave(n=64, end_us=200_000, mailbox_cap=16):
    sc = gossip(n, fanout=4, think_us=2_000, burst=True,
                end_us=end_us, mailbox_cap=mailbox_cap)
    link = Quantize(gossip_links(median_us=20_000, sigma=0.6,
                                 floor_us=8_000), 1_000)
    return sc, link


def _shrink_sched():
    """A degradation window that UNDERCUTS the link's declared 8 ms
    floor (2 ms inside [40 ms, 90 ms))."""
    return FaultSchedule((LinkWindow(None, None, 40_000, 90_000,
                                     scale=0.25),))


def _auto_engine(sc, link, **kw):
    return JaxEngine(sc, link, window="auto", telemetry="counters",
                     lint="off",
                     controller=DispatchController(chunk=8,
                                                   chunk_max=32),
                     **kw)


def _replay_engine(sc, link, decisions, **kw):
    return JaxEngine(sc, link, window="auto", lint="off",
                     controller=DispatchController(
                         mode="replay",
                         replay=DecisionTrace.of(decisions)), **kw)


_GOSSIP = {"nodes": 24, "fanout": 3, "burst": True, "end_us": 90_000,
           "mailbox_cap": 16, "think_us": 700}


def _ctrl_pack():
    from timewarp_tpu.sweep import SweepPack
    return SweepPack.from_json([
        {"id": "gc0", "scenario": "gossip", "params": _GOSSIP,
         "link": "quantize:1000:uniform:3000:9000", "seed": 2,
         "window": "auto", "budget": 100, "controller": "auto"},
        {"id": "gc1", "scenario": "gossip", "params": _GOSSIP,
         "link": "quantize:1000:uniform:3000:9000", "seed": 5,
         "window": "auto", "budget": 60, "controller": "auto"},
        {"id": "goff", "scenario": "gossip", "params": _GOSSIP,
         "link": "quantize:1000:uniform:3000:9000", "seed": 9,
         "window": "auto", "budget": 100},
    ])

"""Device-side event ring buffer: the batched
engine can record per-event ``(time, node, kind, src, payload)``
tuples on-device and they must equal the host oracle's
``record_events=True`` stream record-for-record — so a digest mismatch
at 2^20 nodes is debuggable without a host-oracle rerun at that scale.

Comparison is order-insensitive (sorted): the ring's intra-superstep
order (fires ascending, then deliveries node-major) is deterministic
but deliberately not specified to match the oracle's loop order.
"""

from timewarp_tpu.interp.jax_engine.engine import JaxEngine
from timewarp_tpu.interp.ref.superstep import SuperstepOracle
from timewarp_tpu.models.gossip import gossip
from timewarp_tpu.models.token_ring import token_ring, token_ring_links
from timewarp_tpu.net.delays import Quantize, UniformDelay
from timewarp_tpu.trace.events import assert_traces_equal


def _oracle_view(events, with_src):
    """Oracle events, projected to the ring's schema."""
    out = []
    for e in events:
        if e[0] == "fire":
            out.append(("fire", e[1], e[2]))
        elif e[0] == "recv":
            # ("recv", fire_instant, node, src, deliver_time, pay0)
            out.append(("recv", e[4], e[2], e[3] if with_src else 0,
                        e[5]))
    return sorted(out)


def test_ring_matches_oracle_token_ring_observer():
    """Ordered-inbox scenario with real sender identities."""
    sc = token_ring(24, n_tokens=6, think_us=3_000, bootstrap_us=1_000,
                    end_us=150_000, with_observer=True, mailbox_cap=16)
    link = token_ring_links(24)
    oracle = SuperstepOracle(sc, link, record_events=True)
    otr = oracle.run(500)
    eng = JaxEngine(sc, link, record_events=1 << 14)
    st, etr = eng.run(500)
    assert_traces_equal(otr, etr)
    records, dropped = eng.events(st)
    assert dropped == 0
    assert sorted(records) == _oracle_view(oracle.events,
                                           sc.inbox_src)
    assert any(r[0] == "recv" and r[3] != 0 for r in records)


def test_ring_matches_oracle_windowed_burst_gossip():
    """The sparse adaptive path (windowed + burst + commutative,
    inbox_src=False) records through the same code path."""
    sc = gossip(48, fanout=4, think_us=700, burst=True, end_us=300_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    oracle = SuperstepOracle(sc, link, window=3_000,
                             record_events=True)
    otr = oracle.run(400)
    eng = JaxEngine(sc, link, window=3_000, record_events=1 << 13)
    st, etr = eng.run(400)
    assert_traces_equal(otr, etr)
    records, dropped = eng.events(st)
    assert dropped == 0
    assert sorted(records) == _oracle_view(oracle.events, False)


def test_ring_overflow_counted_never_silent():
    sc = gossip(32, fanout=4, think_us=700, burst=True, end_us=200_000,
                mailbox_cap=16)
    link = Quantize(UniformDelay(3_000, 9_000), 1_000)
    eng = JaxEngine(sc, link, window=3_000, record_events=16)
    st, _ = eng.run(300)
    records, dropped = eng.events(st)
    assert len(records) == 16        # capacity-full ring
    assert dropped > 0               # the excess is counted, not lost
    assert dropped == int(st.ev_count) - 16

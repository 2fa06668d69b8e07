"""What one pass over a praos fleet's state has to move, from its
shapes alone: the numerator of ``praos_fleet_superstep_roofline``. Kept
with the benchmark, beside ``praos_costs.py`` (whose bytes a node and a
word these are, imported and not edited), so that no PR that claims a
gain can change it.

There is no kernel here: an iteration is XLA's, the vmapped superstep
with one rung of the windowed ladder for all the worlds, and most
iterations of a job touch a small part of the nodes. The share prices
an iteration that touched each world's state once; it says how far the
program is from that, not how near a kernel is to its roofline.
"""

from praos_costs import praos_superstep_bytes


def praos_fleet_superstep_bytes(n_nodes: int, worlds: int, mailbox_cap: int,
                                payload_width: int,
                                messages_per_world_iteration: float) -> float:
    """HBM bytes of a fleet's iteration that reads every per-node plane
    and every mailbox plane of every world once and writes them once,
    plus the words of the messages a world sends in an iteration on
    average: ``worlds`` times ``praos_costs.praos_superstep_bytes``.
    2 684 354 560 + 48 a message a world at four worlds of 2^20 nodes,
    24 slots and two payload words: 3 277.6 us at a v5e's 819 GB/s."""
    return int(worlds) * praos_superstep_bytes(
        n_nodes, mailbox_cap, payload_width, messages_per_world_iteration)

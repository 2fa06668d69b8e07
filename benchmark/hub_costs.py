"""What a superstep of the observer ring has to move, from its shapes
alone: the numerator of ``hub_superstep_roofline``. Kept with the
benchmark, beside ``kernel_costs.py`` and ``steady_costs.py`` (which no
later PR edits), so that no PR that claims a gain can change it.

There is no kernel here: the superstep is XLA's, two sorts along the
mailbox's slots, a ladder rung's sort by destination, gathers and
scatters. The share says how far that is from the one pass over the
state that a fused superstep would make.
"""

#: bytes a node of the per-node leaves every superstep reads and
#: writes: ``cnt``, ``val``, ``prev``, ``errs`` (int32), ``send_at``,
#: ``wake`` (int64)
NODE_BYTES = 4 * 4 + 2 * 8
#: a mailbox slot is int32 words: its deliver time (``mb_rel``), its
#: sender (``mb_src``: the ordered inbox with sender ids keeps it) and
#: one column of ``mb_payload`` a payload word
WORD_BYTES = 4


def hub_superstep_bytes(n_nodes: int, mailbox_cap: int,
                        payload_width: int, msgs_per_superstep: float
                        ) -> int:
    """HBM bytes one superstep of the ring with its hub cannot avoid:
    every per-node leaf and every mailbox plane (``[mailbox_cap, n]``
    deliver times, senders and payload words) read once and written
    once, plus the words of the messages a superstep puts into a slot
    (``msgs_per_superstep``: those delivered, a third of a cycle's
    ``n_ring + mailbox_cap``; a note the hub drops is counted and
    never written, so it has no byte here). 21 321 408 at 65 537
    nodes, 8 slots and two payload words: 26.0 us at a v5e's
    819 GB/s."""
    slot = (2 + int(payload_width)) * WORD_BYTES
    return int(int(n_nodes) * 2 * (NODE_BYTES + int(mailbox_cap) * slot)
               + msgs_per_superstep * slot)

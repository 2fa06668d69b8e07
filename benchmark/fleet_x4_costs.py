"""What one iteration of the world-sharded fleet's loop has to move on
one chip, from its shapes alone: the numerator of
``fleet_x4_superstep_roofline``. Kept with the benchmark, beside
``kernel_costs.py``, ``steady_costs.py``, ``praos_costs.py`` and
``ring_x4_costs.py`` (which no later PR edits), so that no PR that
claims a gain can change it.

There is no kernel here: an iteration is XLA's, the vmapped superstep
of the chip's worlds (some hundred fusions, sorts, gathers and
scatters at the rung the chip's busiest world asks for) and the one
``all-reduce`` of the loop's condition. Most iterations of a wave touch
few nodes. The share prices an iteration that touched the chip's worlds
once and waited for no other chip; it says how far the program is from
that, not how near a kernel is to its roofline.

The formula, beside ``steady_costs.steady_superstep_bytes`` (one world,
every node sending: ``n * (2 * (28 + cap * m) + m)``) and
``praos_costs.praos_superstep_bytes`` (one world, the traced jobs' mean
messages: ``n * 2 * (32 + cap * m) + msgs * m``)::

    worlds_local * n * 2 * (NODE_BYTES + cap * m) + msgs * m

with ``m = (1 + payload_width) * 4`` bytes a message and ``msgs`` the
messages one chip's worlds deliver an iteration, at the traced jobs'
mean.
"""

#: bytes a node of the per-node planes every superstep reads and
#: writes: ``hop``, ``lcg``, ``left`` (int32), ``next``, ``wake``
#: (int64): the wave's state is steady mongering's
NODE_BYTES = 3 * 4 + 2 * 8
#: a message in flight is int32 words in the mailbox's planes: its
#: deliver time (``mb_rel``) and one column of ``mb_payload`` a payload
#: word. ``mb_src`` is left out: a scenario that never reads the sender
#: (``inbox_src=False``) never has it written
WORD_BYTES = 4

#: the scope of the liveness reduction in the loop's condition
#: (``sharded.py`` ``ShardedBatchedEngine._any_world``)
LIVENESS_SCOPE = "tw.liveness"


def fleet_x4_superstep_bytes(n_nodes: int, mailbox_cap: int,
                             payload_width: int, worlds_local: int,
                             messages_per_iteration: float) -> float:
    """HBM bytes of an iteration that reads every per-node plane and
    both written mailbox planes (``[mailbox_cap, n]`` deliver times and
    payload words) of each of the chip's ``worlds_local`` worlds once
    and writes them once, plus the words of the messages the chip's
    worlds deliver an iteration. 461 373 440 + 8 a message at eight
    worlds of 2^17 nodes, 24 slots and one payload word: 563.3 us at a
    v5e's 819 GB/s."""
    message = (1 + int(payload_width)) * WORD_BYTES
    return (int(worlds_local) * int(n_nodes) * 2
            * (NODE_BYTES + int(mailbox_cap) * message)
            + float(messages_per_iteration) * message)

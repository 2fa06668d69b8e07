"""What a superstep of the node-sharded edge engine has to move on one
chip, and which operations of a trace are collectives: the constants
of the ``x4_*`` readers (``layer_metrics/x4_*.py``, ``x4_reduce.py``).
Kept with the benchmark, beside ``kernel_costs.py``, ``steady_costs.py``
and ``praos_costs.py`` (which no later PR edits), so that no PR that
claims a gain can change them.

There is no kernel here: the superstep is XLA's, fusions and
collectives under ``shard_map``. The share says how far that is from
one pass over the chip's shard of the state.
"""

#: bytes a node of the per-node leaves of ``EdgeState`` outside the
#: queues: ``cnt``, ``val`` (int32), ``send_at``, ``wake`` (int64)
NODE_BYTES = 2 * 4 + 2 * 8
#: a queue entry is int32 words: its deliver time (``q_rel``) and one
#: word of ``q_pay`` a payload word. ``q_step`` has no entries under a
#: commutative inbox (the ring's)
WORD_BYTES = 4

#: opcodes of the collectives ``MeshComm`` lowers to: ``ppermute``,
#: ``all_gather`` (``all_min``/``all_max``) and ``psum``. An async
#: collective is two operations, ``<opcode>-start`` and
#: ``<opcode>-done``, and one event of the ``Async XLA Ops`` line
COLLECTIVES = ("collective-permute", "all-gather", "all-reduce")
#: the scope of the delivery's ``comm.roll`` calls (``edge_engine.py``)
EXCHANGE_SCOPE = "tw.route/exchange"


def x4_node_bytes(edge_cap: int, payload_width: int = 2,
                  n_edges: int = 1) -> int:
    """Bytes of one node's row of every per-node leaf of ``EdgeState``:
    48 for the lean ring (one in-edge of capacity 2, payload ``[value,
    kind]``)."""
    return NODE_BYTES + int(n_edges) * int(edge_cap) * (
        1 + int(payload_width)) * WORD_BYTES


def x4_superstep_bytes(n_local: int, edge_cap: int = 2,
                       payload_width: int = 2, n_edges: int = 1) -> int:
    """HBM bytes one superstep cannot avoid on one chip: its
    ``n_local`` nodes of every per-node leaf read once and written
    once. 25 165 824 at 2^18 nodes: 30.7 us at a v5e's 819 GB/s. What
    crosses the boundary (one message a shard a superstep on the dense
    ring) is twelve bytes and is not counted."""
    return 2 * int(n_local) * x4_node_bytes(edge_cap, payload_width,
                                            n_edges)

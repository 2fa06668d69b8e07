"""What a full-width superstep of the node-sharded general engine has
to move on one chip and hand to the mesh, from its shapes alone, and
which operations of a trace are its collectives: the constants of the
``a2a_*`` readers (``layer_metrics/a2a_*.py``, ``steady_x4_reduce.py``).
Kept with the benchmark, beside ``steady_costs.py`` and
``ring_x4_costs.py`` (which no later PR edits), so that no PR that
claims a gain can change them.

There is no kernel here: the superstep is XLA's, the one-chip steady
superstep on a quarter of the nodes with a bucketing, ``6 +
payload_width`` ``all_to_all``s and a wider sort and insertion between
its routing and its mailbox. The share says how far that is from one
pass over the chip's shard of the state and one over what it exchanges.
"""

import steady_costs

#: opcodes of the collectives the node-sharded general engine lowers
#: to: ``lax.all_to_all`` (the exchange), ``all_gather``
#: (``all_min``/``all_max``) and ``psum`` (the counters). An async
#: collective is two operations, ``<opcode>-start`` and
#: ``<opcode>-done``, and one event of the ``Async XLA Ops`` line.
#: ``ring_x4_costs.COLLECTIVES`` has no ``all-to-all``
COLLECTIVES = ("all-to-all", "collective-permute", "all-gather",
               "all-reduce")
EXCHANGE_SCOPE = "tw.route/exchange"
BUCKET_SCOPE = "tw.route/exchange/bucket"
SORT_SCOPE = "tw.route/sort"
INSERT_SCOPE = "tw.route/insert"

#: a lane of the exchange is one int8 (whether the slot was written)
#: and int32 words: deliver time, sender, destination, sender-major
#: rank, in-window offset, and one a payload word
LANE_WORDS = 5
WORD_BYTES = 4


def lane_bytes(payload_width: int = 1) -> int:
    """Bytes of one lane of a bucket over all the planes exchanged: 25
    at one payload word."""
    return 1 + WORD_BYTES * (LANE_WORDS + int(payload_width))


def exchange_bytes(shards: int, bucket_cap: int,
                   payload_width: int = 1) -> int:
    """Bytes one device hands the ``all_to_all``s a superstep: its
    ``shards`` buckets of ``bucket_cap`` lanes, full or not. 7 372 800
    at four buckets of 73 728 lanes and one payload word, of which
    ``(shards - 1) / shards`` leave the chip; it receives as many."""
    return int(shards) * int(bucket_cap) * lane_bytes(payload_width)


def superstep_bytes(n_local: int, mailbox_cap: int, payload_width: int,
                    shards: int, bucket_cap: int) -> int:
    """HBM bytes one superstep cannot avoid on one chip: the one-chip
    steady superstep's (``steady_costs.steady_superstep_bytes``) at the
    chip's ``n_local`` nodes, plus the exchange buffers written and
    read once on the way out and written and read once on the way in.
    117 440 512 + 29 491 200 = 146 931 712 at 2^18 nodes, 24 slots,
    four buckets of 73 728 lanes: 179.4 us at a v5e's 819 GB/s."""
    return steady_costs.steady_superstep_bytes(
        n_local, mailbox_cap, payload_width) \
        + 4 * exchange_bytes(shards, bucket_cap, payload_width)

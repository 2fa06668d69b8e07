"""Superstep, XLA: what the four worlds' own senders fill of the rungs
the lockstep made them all take, in percent: the sum over the worlds of
``last_run_stats`` ``world_sender_lanes`` (each world's own active
senders, ``n_active`` before the ``pmax`` that picks one rung for all,
summed over the iterations it stepped) over worlds x ``rung_lanes``
(the rung taken, summed over the iterations), of the traced jobs.
Beside ``rung_lane_occupancy`` (the busiest world's senders over the
rung: what the ladder's geometric steps leave empty), what is missing
here besides is what worlds out of step leave empty in a rung sized
for another. ``None`` from a program that does not count a world's own
senders (the parent of PR 55)."""


def read(trace, run):
    own = rung = 0
    for job in run["jobs"]:
        worlds, lanes = job.get("world_sender_lanes"), job.get("rung_lanes")
        if not worlds or not lanes:
            return None
        own += sum(worlds)
        rung += len(worlds) * lanes
    return 100.0 * own / rung if rung else None

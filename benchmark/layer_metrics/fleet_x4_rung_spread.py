"""Sharding: the share of the pace-setting device's rung lanes that the
other devices did not need, in percent: 1 - mean(``device_rung_lanes``)
/ max(``device_rung_lanes``) of the traced jobs' calls
(``last_run_stats`` of a world-sharded fleet: each device's own sum of
the rungs it took). A rung's cost rises with its width and the devices
meet at the loop's condition, so this is lanes the other devices spend
waiting. ``None`` from a program that does not count a device."""


def read(trace, run):
    by_device = [j.get("device_rung_lanes") for j in run["jobs"]]
    if not by_device or None in by_device:
        return None
    lanes = [sum(col) for col in zip(*by_device)]
    if not lanes or not max(lanes):
        return None
    return 100.0 * (1.0 - sum(lanes) / len(lanes) / max(lanes))

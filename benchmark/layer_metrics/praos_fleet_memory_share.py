"""Device: the run's peak device memory as a share of the chip's HBM,
in percent: ``memory_peak_bytes`` (``device.memory_stats()
["peak_bytes_in_use"]`` after the window, read by the builder before
the comparison's own calls and handed over in ``Cell.facts()``) over
``peaks.json`` ``hbm_gb``. The first cell in which three live states
and the ladder's temporaries can fail to fit: lower leaves room for
more worlds a chip. Nothing to read where the backend reports no
memory statistics (XLA:CPU) or the device has no published size."""


def read(trace, run):
    peak = run["facts"].get("memory_peak_bytes")
    if not peak or not run["peaks"] or not run["peaks"].get("hbm_gb"):
        return None
    return 100.0 * peak / (run["peaks"]["hbm_gb"] * 1e9)

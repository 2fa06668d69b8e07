"""Messages delivered by the jobs of the window (each ended by a host
readback; jobs that failed their gates count nothing) over the
window's wall seconds."""


def read(window):
    return sum(j["msgs"] for j in window["jobs"]) / window["window_s"]

"""Entry points: seconds JAX spent tracing, lowering and compiling (or
fetching from the persistent cache) during set-up, from JAX's own
monitoring events."""


def read(trace, run):
    return run["compile_seconds"]

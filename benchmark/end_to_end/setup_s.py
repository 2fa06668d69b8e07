"""Process start to the first job of the window: imports, tracing,
lowering, compile or cache load, state built from the seed, warm-up."""


def read(window):
    return window["set_up_seconds"]

"""Share of the traced slice in which no operation ran on the chip."""

from bench import tracing


def read(x):
    busy = tracing.busy_seconds(x.trace)
    if busy is None or x.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / x.trace.window_s)

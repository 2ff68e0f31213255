"""Mean ``lock_wait`` span per stepped tick (``FlightRecorder``):
``IngestServer.tick`` from entry to holding ``StreamServer.pool_lock``
and ``queue_lock``, before its pops.  No decode runs under either lock,
so it waits for another thread's bookkeeping or a control frame; over
the ticks of the window before the traced slice."""

from bench import spans


def read(x):
    return spans.tick_ms(x.ticks, "lock_wait")

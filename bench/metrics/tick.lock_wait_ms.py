"""Mean ``lock_wait`` span per stepped tick (``FlightRecorder``):
``IngestServer.tick`` waiting for the ingest lock, which a data frame's
decode holds; over the ticks of the window before the traced slice."""

from bench import spans


def read(x):
    return spans.tick_ms(x.ticks, "lock_wait")

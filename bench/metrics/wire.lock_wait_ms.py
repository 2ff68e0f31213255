"""Mean ``wire.lock_wait`` span per data frame (``FlightRecorder`` chunk
records): ``IngestServer.handle_message`` from entry to holding the ingest
lock, which the tick holds while it runs; over the chunks popped by the
ticks of the window before the traced slice."""

from bench import spans


def read(x):
    return spans.chunk_ms(x.ticks, "wire.lock_wait")

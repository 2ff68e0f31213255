"""Mean ``wire.lock_wait`` span per data frame (``FlightRecorder`` chunk
records): ``IngestServer.handle_message`` from the end of the frame's
decode to holding ``StreamServer.queue_lock`` (the ingest lock), which a
tick holds for its pops and idle evictions, not for its step; over the
chunks popped by the ticks of the window before the traced slice."""

from bench import spans


def read(x):
    return spans.chunk_ms(x.ticks, "wire.lock_wait")

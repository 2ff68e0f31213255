"""Mean ``wire.decode`` span per data frame (``FlightRecorder`` chunk
records): ``codec.decode_message`` (field table, CRC32 over the payload,
zero-copy views) as the ingest server times it; over the chunks popped
by the ticks of the window before the traced slice.  The inside twin of
``wire.decode_ms``."""

from bench import spans


def read(x):
    return spans.chunk_ms(x.ticks, "wire.decode")

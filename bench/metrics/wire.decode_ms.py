"""Host milliseconds per data frame in ``codec.decode_message`` (field
table, CRC32 over the payload, zero-copy views), timed by the benchmark
around each call the ingest server makes in the window before the traced
slice (the profiler is off there)."""


def read(x):
    if not x.decode_s:
        return None
    return 1e3 * sum(x.decode_s) / len(x.decode_s)

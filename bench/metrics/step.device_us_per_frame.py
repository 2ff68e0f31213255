"""Device-busy microseconds per frame of the stepped streams: the union
of the intervals in which an operation ran on the chip, over the traced
slice, divided by the frames read back in it."""

from bench import tracing


def read(x):
    frames = len(x.stepped) * x.cfg["chunk_frames"]
    busy = tracing.busy_seconds(x.trace)
    if not frames or not busy:
        return None
    return 1e6 * busy / frames

"""Mean ``queue.wait`` span per stepped chunk (``FlightRecorder`` chunk
records): from the chunk's enqueue to the pop of the tick that steps
it; over the chunks popped by the ticks of the window before the traced
slice."""

from bench import spans


def read(x):
    return spans.chunk_ms(x.ticks, "queue.wait")

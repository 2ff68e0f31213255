"""Mean ``stack`` span per stepped tick (``FlightRecorder``), a child of
``dispatch``: the row assembly and the ``jnp.stack`` of the tick's batch
(the host-to-device copy); over the ticks of the window before the
traced slice."""

from bench import spans


def read(x):
    return spans.tick_ms(x.ticks, "stack")

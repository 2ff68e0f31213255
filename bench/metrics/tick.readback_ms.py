"""Mean ``readback`` span per stepped tick (``FlightRecorder``): waiting
for the device and the tick's one batched ``device_get``; over the ticks
of the window before the traced slice."""


def read(x):
    per_tick = [
        sum(b - a for name, a, b in tk["spans"] if name == "readback")
        for tk in x.ticks
        if any(name == "readback" for name, _, _ in tk["spans"])
    ]
    if not per_tick:
        return None
    return 1e3 * sum(per_tick) / len(per_tick)

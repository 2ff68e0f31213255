"""Mean ``dispatch`` span per stepped tick (``FlightRecorder``): stacking
the tick's rows, the host-to-device copy and the rung step launches; over
the ticks of the window before the traced slice."""


def read(x):
    per_tick = [
        sum(b - a for name, a, b in tk["spans"] if name == "dispatch")
        for tk in x.ticks
        if any(name == "dispatch" for name, _, _ in tk["spans"])
    ]
    if not per_tick:
        return None
    return 1e3 * sum(per_tick) / len(per_tick)

"""Lanes computed by the calls of the window, in millions, over the
window (host clock).  The window runs until the last call returns."""


def read(reading):
    if not reading.records or reading.window.seconds <= 0:
        return None
    return sum(r.elements for r in reading.records) / 1e6 / \
        reading.window.seconds

"""Arithmetic shared by the metric readers under ``metrics/``."""


def idle_pct(reading):
    """Share of the traced window in which no operation ran on the chip."""
    t = reading.trace
    if t is None or t.busy_s <= 0 or t.window_s <= 0:
        return None
    return 100.0 * t.idle_share


def hbm_roofline_pct(reading):
    """Bytes the window's work requires, over what the chip's HBM could
    move in the time the chip was busy.  The bytes come from the request
    shapes (each driver's ``required_bytes``), not from the program, so
    the share reads the same work whatever implements it."""
    t = reading.trace
    peak = reading.peaks.get("hbm_bytes_per_s")
    if t is None or t.busy_s <= 0 or not peak:
        return None
    need = sum(r.required_bytes for r in reading.records if r.error is None)
    return 100.0 * need / (peak * t.busy_s)

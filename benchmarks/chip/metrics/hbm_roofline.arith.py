"""Required bytes over peak HBM bandwidth times device-busy seconds, in %
(device trace; see ``readings.py``)."""

from readings import hbm_roofline_pct as read  # noqa: F401

"""Required bytes over peak HBM bandwidth times device-busy seconds, in %
(device trace; see ``readings.py``): 4 bytes a word of the region
written and 4 bytes a group's source word read (``required_bytes`` of
``drivers/mrc_erase.py``)."""

from readings import hbm_roofline_pct as read  # noqa: F401

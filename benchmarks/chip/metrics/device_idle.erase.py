"""Device idle share of the traced window, in % (device trace)."""

from readings import idle_pct as read  # noqa: F401

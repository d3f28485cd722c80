"""Share of the profiled stretch in which no operation ran on the chip."""
from benchlib.readers import idle_share_pct as read  # noqa: F401

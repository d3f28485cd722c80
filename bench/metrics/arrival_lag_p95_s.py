"""95th percentile, over every request due in the window, of the seconds
from its due time to the serve loop's taking it up (the program's
enqueue event against the due time it stamps there): the loop's lag in
noticing arrivals, which a long dispatch holds up."""
from benchlib.attention import arrival_lag_p95_s as read  # noqa: F401

"""Share of its roofline that the paged decode attention kernel reaches in
the profiled stretch: the least time its calls could take (query-key
operations at the bf16 peak or KV-pool bytes at the memory bandwidth,
whichever is longer, from the serve loop's attention counters of each
fused decode iteration) over the device time they took."""
from benchlib.attention import decode_roofline_pct as read  # noqa: F401

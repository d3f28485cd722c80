"""Share of its roofline that the int8-weight matmul kernel reaches in
the profiled stretch: the least time its calls could take (operations at
the bf16 peak or bytes at the memory bandwidth, whichever is longer,
from each call's shape) over the device time they took."""
from benchlib.readers import quant_matmul_roofline_pct as read  # noqa: F401

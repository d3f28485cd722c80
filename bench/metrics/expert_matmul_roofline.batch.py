"""Share of its roofline that the grouped expert matmul kernel reaches in
the profiled stretch: the least time its calls could take (operations at
the bf16 peak or expert-weight and row bytes at the memory bandwidth,
whichever is longer, from the serve loop's routing counters of each
iteration) over the device time they took."""
from benchlib.moe import expert_matmul_roofline_pct as read  # noqa: F401

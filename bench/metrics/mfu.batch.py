"""Share of the chip's bf16 peak that the step programs reach in the
profiled stretch: weight-matmul operations of the real tokens they
processed over their device time (attention's products left out)."""
from benchlib.readers import mfu_pct as read  # noqa: F401

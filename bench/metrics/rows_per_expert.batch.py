"""Token-expert rows per expert hit over the window's busy iterations
(the serve loop's routing counters): how many rows share each streaming
of an expert's weights, the expert matmul's batch amortisation."""
from benchlib.moe import rows_per_expert as read  # noqa: F401

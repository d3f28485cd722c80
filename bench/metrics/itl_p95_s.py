"""95th percentile of every gap between output tokens of the window's
requests, as the program records it at each host sync (ServeMetrics.itl_s;
a fused multi-step sync spreads its wall time over its tokens)."""
from benchlib.readers import nearest_rank


def read(run):
    return nearest_rank(run.metrics.itl_s, 0.95)

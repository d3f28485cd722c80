"""95th percentile, over every request due in the window, of the
seconds from its due time to its first token (the tracer's first_token
event, on the serve clock the arrivals are scheduled on).  A request
with no first token counts as missing: infinitely late."""
import math

from benchlib.readers import nearest_rank, ttft


def read(run):
    v = nearest_rank(ttft(run), 0.95)
    return None if v is None or math.isinf(v) else v

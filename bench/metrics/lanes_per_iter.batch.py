"""Mean decode lanes per serve-loop iteration that ran work (the
tracer's iteration records): how full the scheduler keeps the batch."""
from benchlib.readers import busy_iterations


def read(run):
    it = busy_iterations(run)
    return sum(e["decode_lanes"] for e in it) / len(it) if it else None

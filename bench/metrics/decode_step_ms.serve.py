"""Device milliseconds per decode step of the fused decode program
(its executions in the profiled stretch, over the steps each fuses)."""
from benchlib.readers import DECODE, STEPS_PER_SYNC


def read(run):
    red = run.reduction
    ex = red.executions(DECODE) if red is not None else []
    if not ex:
        return None
    return 1000.0 * sum(m[2] for m in ex) / (len(ex) * STEPS_PER_SYNC)

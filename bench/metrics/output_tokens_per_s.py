"""Output tokens of every request served, over the whole wall time of
the measured serve call, the drain of running requests included."""


def read(run):
    return run.metrics.generated_tokens / run.wall_s

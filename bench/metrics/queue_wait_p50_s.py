"""Median seconds from a request's due time to its admission into a
slot (the tracer's admit event): the scheduler's queue."""
from benchlib.readers import nearest_rank


def read(run):
    due = {it.uid: it.arrival for it in run.items}
    return nearest_rank([e["t"] - due[e["uid"]] for e in run.events("admit")
                         if e["resume"] == "no"], 0.5)

"""Mean host milliseconds per serve-loop iteration that ran work: the
iteration's wall time outside its blocking device dispatches."""
from benchlib.readers import host_ms_per_iter as read  # noqa: F401

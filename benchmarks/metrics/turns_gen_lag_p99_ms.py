"""How late the load generator handed requests over: hand-over minus due time, 99th percentile."""
from benchlib.readers import gen_lag_p99_ms as read  # noqa: F401

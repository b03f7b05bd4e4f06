"""`admitted` to `first_token` on `serving.request`, 90th percentile over the requests admitted in the window."""
from benchlib import program_spans as P

read = P.admit_to_first_token_ms_p90

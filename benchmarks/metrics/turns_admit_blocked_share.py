"""Requests admitted in the window that sat through at least one admit pass unadmitted."""
from benchlib import program_spans as P

read = P.admit_blocked_share

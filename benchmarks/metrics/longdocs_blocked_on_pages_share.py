"""Of the window's admit passes that left their head queued, those that left it for want of pages."""
from benchlib import program_spans as P

read = P.blocked_on_pages_share

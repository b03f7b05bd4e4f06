"""Prompt tokens served from the prefix cache over prompt tokens."""
from benchlib.readers import prefix_hit_share as read  # noqa: F401

"""Mean share of the engine's slots in use per engine step (allocator, sampled after each step)."""
from benchlib.readers import batch_occupancy as read  # noqa: F401

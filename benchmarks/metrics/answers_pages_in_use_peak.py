"""Peak of PageAllocator.in_use, sampled after each engine step: latent rows, 16 tokens a page."""
from benchlib.readers import pages_in_use_peak as read  # noqa: F401

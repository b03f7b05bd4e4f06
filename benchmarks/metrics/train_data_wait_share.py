"""Share of the window the step loop waited in next() of the input pipeline."""
from benchlib.readers import data_wait_share as read  # noqa: F401

"""Programs built after the window opened and before it closed."""
from benchlib.readers import recompiles_in_window as read  # noqa: F401

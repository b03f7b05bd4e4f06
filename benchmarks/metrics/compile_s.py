"""Seconds jax traced, lowered and compiled or loaded programs before the window."""
from benchlib.readers import compile_s as read  # noqa: F401

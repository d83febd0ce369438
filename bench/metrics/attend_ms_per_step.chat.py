"""attend_ms_per_step of the chat cells; see phases.attend_ms_per_step."""
from phases import attend_ms_per_step as read  # noqa: F401

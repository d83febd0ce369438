"""attend_ms_per_step of the long mixed cell; see phases.attend_ms_per_step."""
from phases import attend_ms_per_step as read  # noqa: F401

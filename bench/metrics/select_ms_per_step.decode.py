"""select_ms_per_step of the decode cells; see phases.select_ms_per_step."""
from phases import select_ms_per_step as read  # noqa: F401

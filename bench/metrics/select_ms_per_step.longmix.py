"""select_ms_per_step of the long mixed cell; see phases.select_ms_per_step."""
from phases import select_ms_per_step as read  # noqa: F401

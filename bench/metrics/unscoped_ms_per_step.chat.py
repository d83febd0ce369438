"""unscoped_ms_per_step of the chat cells; see phases.unscoped_ms_per_step."""
from phases import unscoped_ms_per_step as read  # noqa: F401

"""unscoped_ms_per_step of the long mixed cell; see phases.unscoped_ms_per_step."""
from phases import unscoped_ms_per_step as read  # noqa: F401

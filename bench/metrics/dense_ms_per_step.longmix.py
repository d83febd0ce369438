"""dense_ms_per_step of the long mixed cell; see phases.dense_ms_per_step."""
from phases import dense_ms_per_step as read  # noqa: F401

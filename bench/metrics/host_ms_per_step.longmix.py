"""host_ms_per_step of the long mixed cell; see readers.host_ms_per_step."""
from readers import host_ms_per_step as read  # noqa: F401

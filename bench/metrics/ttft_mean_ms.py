"""ttft_mean_ms; see readers.ttft_mean_ms."""
from readers import ttft_mean_ms as read  # noqa: F401

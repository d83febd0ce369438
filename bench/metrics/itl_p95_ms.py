"""itl_p95_ms; see readers.itl_p95_ms."""
from readers import itl_p95_ms as read  # noqa: F401

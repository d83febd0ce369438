"""mfu of the prefill cells; see readers.mfu."""
from readers import mfu as read  # noqa: F401

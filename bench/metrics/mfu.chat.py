"""mfu of the chat cells; see readers.mfu."""
from readers import mfu as read  # noqa: F401

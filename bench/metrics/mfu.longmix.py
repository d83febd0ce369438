"""mfu of the long mixed cell; see readers.mfu."""
from readers import mfu as read  # noqa: F401

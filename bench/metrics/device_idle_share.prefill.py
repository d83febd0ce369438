"""device_idle_share of the prefill cells; see readers.device_idle_share."""
from readers import device_idle_share as read  # noqa: F401

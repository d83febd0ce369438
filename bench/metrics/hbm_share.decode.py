"""hbm_share of the decode cells; see readers.hbm_share."""
from readers import hbm_share as read  # noqa: F401

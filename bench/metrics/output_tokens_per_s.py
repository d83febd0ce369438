"""output_tokens_per_s; see readers.output_tokens_per_s."""
from readers import output_tokens_per_s as read  # noqa: F401

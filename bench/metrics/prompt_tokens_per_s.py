"""prompt_tokens_per_s; see readers.prompt_tokens_per_s."""
from readers import prompt_tokens_per_s as read  # noqa: F401

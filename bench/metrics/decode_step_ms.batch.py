"""Paged forward, decode in the batch cell, moving ``output_tokens_per_s``;
see ``readers.decode_step_ms``."""
from readers import decode_step_ms as read  # noqa: F401

"""Paged forward, decode in the chat cell, moving ``tpot_p90_ms``; see
``readers.decode_step_ms``."""
from readers import decode_step_ms as read  # noqa: F401

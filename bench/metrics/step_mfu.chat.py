"""Whole step: model FLOP utilisation in the chat cell, moving
``tpot_p90_ms``; see ``readers.step_mfu``."""
from readers import step_mfu as read  # noqa: F401

"""Whole step: model FLOP utilisation in the batch cell, moving
``output_tokens_per_s``; see ``readers.step_mfu``."""
from readers import step_mfu as read  # noqa: F401

"""Kernels: paged decode attention roofline share in the chat cell, moving
``tpot_p90_ms``; see ``readers.decode_attn_roofline``."""
from readers import decode_attn_roofline as read  # noqa: F401

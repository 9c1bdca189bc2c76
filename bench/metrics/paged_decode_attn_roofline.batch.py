"""Kernels: paged decode attention roofline share in the batch cell, moving
``output_tokens_per_s``; see ``readers.decode_attn_roofline``."""
from readers import decode_attn_roofline as read  # noqa: F401

"""Device idle share in the chat cell: 1 - the union of the intervals in
which an operation ran on the chip over the traced window, in percent."""
from readers import idle_share as read  # noqa: F401

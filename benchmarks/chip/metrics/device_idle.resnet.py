"""Device idle share of the traced window: 1 minus the union of
device-op intervals over its length."""
from readers import idle_share as read  # noqa: F401

"""The gossip kernels' share of the HBM roofline: 16 bytes per parameter
per worker per call over their summed device time."""
from readers import gossip_roofline as read  # noqa: F401

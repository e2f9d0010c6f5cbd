"""The whole gradient-training step's share of the chips' bf16 peak,
from model FLOPs per unit of work and the work the traced window did."""
from readers import mfu as read  # noqa: F401

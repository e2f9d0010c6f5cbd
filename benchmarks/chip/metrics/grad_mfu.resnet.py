"""The model's own share of the chips' bf16 peak: model FLOPs of the
traced window's gradient ticks over the device time of ``replay.grad``
(forward and backward), the bank work around it taken out."""
from scopes import grad_mfu


def read(r, facts):
    return grad_mfu(r.get("scope_s", {}), facts)

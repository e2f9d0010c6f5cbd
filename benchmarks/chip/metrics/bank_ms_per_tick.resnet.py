"""Device milliseconds per gradient tick of the flat-bank work: unpack,
pack, the SGD step on x and x~, and the mixing sweeps."""
from scopes import bank_ms_per_tick


def read(r, facts):
    return bank_ms_per_tick(r.get("scope_s", {}), facts)

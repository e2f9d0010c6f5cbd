"""Device milliseconds per gradient tick of the program's in-scan
bookkeeping (loss, consensus distance, mean norm)."""
from scopes import record_ms_per_tick


def read(r, facts):
    return record_ms_per_tick(r.get("scope_s", {}), facts)

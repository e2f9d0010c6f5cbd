"""Device milliseconds per gradient tick of the expert layers: the router
(``model.router``: the norm, affinities, selection, weights), the held
routed experts (``model.experts``: sort, gather, grouped matmuls,
combine) and the shared experts (``model.shared``), forward and
backward."""

SCOPES = ("model.router.", "model.experts.", "model.shared.")


def read(r, facts):
    moe = [v for k, v in r.get("model_s", {}).items()
           if k.startswith(SCOPES)]
    if not moe or facts["grad_ticks"] <= 0:
        return None
    return 1e3 * sum(moe) / facts["grad_ticks"]

"""Device milliseconds per gradient tick of the stand-in's ``model.mlp``
scope, forward and backward: a reader of a model's own scopes."""


def read(r, facts):
    mlp = [v for k, v in r.get("model_s", {}).items()
           if k.startswith("model.mlp.")]
    if not mlp or facts["grad_ticks"] <= 0:
        return None
    return 1e3 * sum(mlp) / facts["grad_ticks"]

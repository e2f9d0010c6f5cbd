"""Device milliseconds per gradient tick of the latent attention,
``model.mla`` (its norm, projections, RoPE, attention and output
projection), forward and backward: a reader of the model's own scopes."""


def read(r, facts):
    mla = [v for k, v in r.get("model_s", {}).items()
           if k.startswith("model.mla.")]
    if not mla or facts["grad_ticks"] <= 0:
        return None
    return 1e3 * sum(mla) / facts["grad_ticks"]

"""A number the run itself counted or clocked (``facts``), optionally
divided by another and scaled. params: {"fact", "per"?, "scale"?}."""


def read(ctx, params):
    facts = ctx["facts"]
    v = facts.get(params["fact"])
    if v is None:
        return None
    if "per" in params:
        d = facts.get(params["per"])
        if not d:
            return None
        v = v / d
    return v * params.get("scale", 1.0)

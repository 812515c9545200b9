"""Device time from the traced chunk: the busy union, or the summed
duration of the leaf operations whose names match (or, with "invert",
do not match) the patterns. params: {"match"?: [regex], "invert"?: bool,
"per"?: fact to divide by, "scale"?}. Nothing traced, or no operation on
the device, reads nothing."""


def seconds(ctx, params):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    if "match" in params:
        s = tr.matched_s(params["match"], bool(params.get("invert")))
        return s if s else None
    return tr.busy_s if tr.busy_s > 0 else None


def read(ctx, params):
    s = seconds(ctx, params)
    if s is None:
        return None
    if "per" in params:
        d = ctx["facts"].get(params["per"])
        if not d:
            return None
        s = s / d
    return s * params.get("scale", 1.0)

"""Share of the traced window in which no operation ran on the device,
in per cent."""


def read(ctx, params):
    tr = ctx.get("trace")
    if tr is None or not tr.devices or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)

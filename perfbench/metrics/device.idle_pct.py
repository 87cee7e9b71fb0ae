"""1 - union of device-op intervals over the traced window, on the fullest device."""


def read(ctx):
    tr = ctx["trace"]
    if tr["window_s"] <= 0 or tr["busy_fullest_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_fullest_s"] / tr["window_s"])

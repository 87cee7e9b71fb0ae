"""Device time of jit(train) in the traced window, per gradient step."""


def read(ctx):
    prog = ctx["trace"]["programs"].get("jit_train")
    g = ctx["window"]["grad_steps"]
    if not prog or g <= 0:
        return None
    return 1e3 * prog["seconds"] / g

"""Share of the traced window inside the program's `Time/train_time` host spans
(the span times the dispatch of a burst, never its device time)."""


def read(ctx):
    tr = ctx["trace"]
    s = tr["spans_s"].get("Time/train_time")
    if s is None or tr["window_s"] <= 0:
        return None
    return 100.0 * s / tr["window_s"]

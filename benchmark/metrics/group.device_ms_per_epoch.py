"""Device milliseconds per epoch inside the traced window: the device
time of the group programs that ran whole inside it, over the epochs
they scanned."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("epochs"):
        return None
    return 1e3 * t["group_busy_s"] / t["epochs"]

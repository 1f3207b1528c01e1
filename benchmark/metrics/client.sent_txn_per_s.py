"""What the generators offered inside the window, per second."""


def read(ctx):
    return sum(c["win_sent"] for c in ctx["clients"]) / ctx["seconds"]

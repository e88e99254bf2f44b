"""A reader of its own: candidates the window's last call returned."""


def read(ctx):
    result = ctx["calls"][-1].result
    return None if result is None else float(len(result[0]))

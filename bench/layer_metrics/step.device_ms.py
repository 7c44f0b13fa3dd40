"""Device busy time per engine step in the traced part of the window:
the union of device operation intervals over the `bench.step` spans."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.steps:
        return None
    return 1e3 * tr.busy_s / tr.steps

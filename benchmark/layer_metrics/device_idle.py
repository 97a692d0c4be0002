"""1 - (union of device-operation intervals) / traced window, in percent.
One reader for ``device_idle.train`` and ``device_idle.serve``: the same
quantity, split because the cells report different end-to-end metrics."""


def read(name, ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

"""Executor registered -> first optimizer step pushed: backend start-up,
state init, compile or cache load, first batch (job history)."""


def read(name, ctx):
    lat = ctx["observed"].get("submit_latency")
    if not lat or "registered_s" not in lat or "first_step_s" not in lat:
        return None
    return float(lat["first_step_s"] - lat["registered_s"])

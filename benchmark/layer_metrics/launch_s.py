"""Client -> AM -> executor registered, from the job history (the program's
own ``am.events.submit_latency()``)."""


def read(name, ctx):
    lat = ctx["observed"].get("submit_latency")
    return None if not lat or "registered_s" not in lat else float(lat["registered_s"])

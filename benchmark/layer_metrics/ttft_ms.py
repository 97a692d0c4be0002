"""Other statistics of the window's times to first token, over every request
submitted in it, beside the end-to-end p75: ``ttft_ms.mean`` and
``ttft_ms.p90`` (nearest rank). With some 45 requests in a window one late
request moves the mean by 2%, and p90 sits on the edge of the largest prefill
bucket's plateau; they are reported, not bounded."""


def read(name, ctx):
    value = (ctx["observed"].get("ttft_ms") or {}).get(name.split(".", 1)[1])
    return None if value is None else float(value)

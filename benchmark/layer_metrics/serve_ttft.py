"""``serve_ttft.<part>_ms``: a request's time to first token as its caller
feels it, tiled where it happens — the mean, over the ``serve.visible``
markers of the traced seconds (one a request, written by the engine at the very
end of the ``step()`` call that admitted it; benchmark/layer_metrics/
serve_markers.py), of one of its five consecutive parts:

    queue_ms     submit() -> the admission round that dequeues it begins
    behind_ms    round begins -> its own prefill program is dispatched (the
                 other admissions of the round run first; prefix match, the
                 slot's state zeroed, blocks planned)
    prefill_ms   prefill dispatched -> its first token on the host
    activate_ms  token on the host -> the slot is activated
    held_ms      activated -> step() returns to its caller (the decode step,
                 and a step dispatched ahead, that the same call runs first)

The five add up to the mean of what ``ttft_p75_ms`` is a percentile of, but
for what the loop that drives the engine adds after ``step()`` returns. Nothing
where the capture holds no such marker (a program that writes none).

A SAMPLE, not the yardstick: the traced seconds hold 10-24 requests, and each
part is bimodal (the first admission of a round waits half a millisecond
behind, the second a whole prefill). The engine's means over the whole window
(``stats_snapshot()["ttft_<part>_mean_s"]``) are what a change is judged by;
they reach the result line once a ``benchmark`` PR lets a driver copy them
(PERF.md section 7)."""

from benchmark.layer_metrics import serve_markers


def read(name, ctx):
    part = name.split(".", 1)[1].removesuffix("_ms") + "_us"
    values = [args[part] for marker, _, args in serve_markers.of_run(ctx)
              if marker == serve_markers.VISIBLE and part in args]
    mean_us = serve_markers.mean(values)
    return None if mean_us is None else mean_us / 1e3

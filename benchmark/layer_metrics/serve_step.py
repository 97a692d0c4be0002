"""``serve_step.<member>``: the decode steps of the traced seconds by why each
ran as it did, from the ONE marker the engine writes a step (named by the
reason, benchmark/layer_metrics/serve_markers.py):

    kept_finish_share        steps kept because a row was on its last token
                             (``serve.kept_finish``) / all step markers, %
    kept_admit_share         steps kept because a request was queued and a
                             slot free (``serve.kept_admit``) / all, %
    gap_ms_ahead             mean visible gap (``gap_us``: the emit before ->
                             this emit, one inter-token sample) of the steps
                             dispatched ahead with no admission beside them
    gap_ms_kept              the same of the steps that were NOT dispatched
                             ahead (``serve.kept_*`` and ``serve.fresh``),
                             less what the admissions of the same call took
                             (``admit_us``; 0 where there were none): their
                             difference is what a step that could not run
                             ahead costs
    gap_ms_beside_admission  mean visible gap of the steps whose call ran a
                             prefill first (``admitted`` >= 1): what a token
                             beside an admission waits

Nothing where the capture holds no step marker, or none of the member's kind."""

from benchmark.layer_metrics import serve_markers


def read(name, ctx):
    member = name.split(".", 1)[1]
    steps = [(marker, args) for marker, _, args in serve_markers.of_run(ctx)
             if marker in serve_markers.STEP_MARKERS and "gap_us" in args]
    if not steps:
        return None
    if member.endswith("_share"):
        kind = "serve." + member.removesuffix("_share")
        return 100.0 * sum(1 for marker, _ in steps if marker == kind) / len(steps)
    if member == "gap_ms_ahead":
        gaps = [a["gap_us"] for marker, a in steps
                if marker == "serve.ahead" and not a.get("admitted")]
    elif member == "gap_ms_kept":
        gaps = [a["gap_us"] - a.get("admit_us", 0) for marker, a in steps
                if marker != "serve.ahead"]
    elif member == "gap_ms_beside_admission":
        gaps = [a["gap_us"] for _, a in steps if a.get("admitted", 0) >= 1]
    else:
        return None
    mean_us = serve_markers.mean(gaps)
    return None if mean_us is None else mean_us / 1e3

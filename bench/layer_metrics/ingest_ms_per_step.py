"""Milliseconds of the program's ingest plane per batched step: the
program's own ``ingest`` spans (the body of
``StreamScheduler.push_audio_batch``) in the window, summed and divided
by its ``hop`` spans.  None where the program records no ``ingest``
span."""


def read(ctx):
    steps = sum(1 for s in ctx.spans if s["name"] == "hop")
    ingest = [s["dur_s"] for s in ctx.spans if s["name"] == "ingest"]
    if not steps or not ingest:
        return None
    return 1e3 * sum(ingest) / steps

"""Host milliseconds of the scheduler per batched step: the program's own
``pack``, ``dispatch``, ``detector`` and ``push_fold`` spans of
``step_batch``, summed over the window and divided by its ``hop`` spans.
The ``device`` span (the wait on the fence) is left out."""
HOST = ("pack", "dispatch", "detector", "push_fold")


def read(ctx):
    steps = sum(1 for s in ctx.spans if s["name"] == "hop")
    if not steps:
        return None
    host = sum(s["dur_s"] for s in ctx.spans if s["name"] in HOST)
    return 1e3 * host / steps

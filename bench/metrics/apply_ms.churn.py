"""Update path, end to end on the host: mean wall time of the event
source's ``apply_updates`` calls over the batches applied in the window
(the source's own record, ``ctx["events"]``)."""


def read(ctx):
    rec = ctx.get("events") or {}
    s = rec.get("apply_s")
    return 1e3 * sum(s) / len(s) if s else None

"""FL data: mean host time of a round's ``hfl.data`` span (windowing of
every client's training and validation data, and its upload), over the
window's untraced rounds, in ms.  Reads ``ctx["program_spans"]``, the
program's wall spans (``repro.telemetry.Span``) of those rounds."""


def read(ctx):
    durs = [sp.dur for sp in ctx.get("program_spans") or ()
            if sp.name == "hfl.data"]
    return 1e3 * sum(durs) / len(durs) if durs else None

"""FL aggregation: bytes of the client models sent to their aggregators
(``hfl.model_upload_bytes``: whole GRUs, or an LM's adapters) over the
rounds run (``hfl.rounds.*``), both counted by the program over the
window's untraced rounds, in MB of 1e6 bytes.  Reads
``ctx["program_counters"]``, each counter's increase over those rounds."""


def read(ctx):
    counters = ctx.get("program_counters") or {}
    rounds = sum(v for k, v in counters.items()
                 if k.startswith("hfl.rounds."))
    sent = counters.get("hfl.model_upload_bytes")
    return sent / rounds / 1e6 if rounds and sent is not None else None

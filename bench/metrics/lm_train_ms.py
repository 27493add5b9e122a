"""FL client, LM cells: device time per round of the program that takes
every LM client's local steps (``train_lm_clients``), from the profiler
trace."""


def read(ctx):
    summary, rounds = ctx.get("trace"), ctx.get("traced_rounds")
    if not summary or not rounds:
        return None
    t = sum(v for k, v in summary["program_s"].items()
            if "train_lm_clients" in k)
    return t / rounds * 1e3 if t > 0 else None

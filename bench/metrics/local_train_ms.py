"""FL client: device time per round of the program that trains every
client locally (``train_clients_locally``), from the profiler trace."""


def read(ctx):
    summary, rounds = ctx.get("trace"), ctx.get("traced_rounds")
    if not summary or not rounds:
        return None
    t = sum(v for k, v in summary["program_s"].items()
            if "train_clients_locally" in k)
    return t / rounds * 1e3 if t > 0 else None

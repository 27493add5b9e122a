"""FL client, LM cells: ``hfl_mfu``'s share of the chip's bf16 peak, of
the operations an LM round needs (every client's local steps and its
held-out evaluation, ``bench/lm_flops.py``) in place of the GRU's."""
from bench import loader


def read(ctx):
    return loader.metric_reader("hfl_mfu").read(
        dict(ctx, round_flops=ctx.get("lm_round_flops")))

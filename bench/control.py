"""Readings that set the limits of ``correct``: the program's sound runs
and the control's, several seeds in one process (set-up is long).

    python bench/control.py --workload serve-conv --seeds 1,2,3 --seconds 20

For each seed it runs the cell as ``bench/run.py`` does, at the cell's
own load, and then, beside the program's reading, the control's: for a
serving cell the plain reference in fp8 (one precision step below the
configuration's bf16), reading at each position of the same prompts and
served tokens the gap of the token fp8 puts first; for an HFL cell the
reference in bf16 and the reference with a fault planted (a round that
returns its state, half of each batch, no aggregation).  Prints one JSON
line per seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax

    from bench import common, loader, run
    from repro.launch.compile_cache import enable_compile_cache

    cell = loader.workload(args.workload)
    try:
        devices = common.device_info(int(cell["chips"]))
    except common.NoChip as e:
        common.log(f"control: {e}")
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        ns = SimpleNamespace(workload=args.workload, seed=seed,
                             seconds=args.seconds, trace=0)
        result, checks, ctx = run.execute(ns, devices, control=True)
        seen = {k: ctx[k] for k in ("readings", "control") if k in ctx}
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "checks": checks.as_dict(), **seen}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

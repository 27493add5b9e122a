"""Records ``bench/testdata/small.xplane.pb`` on a TPU: two jitted
programs (one with async copies that overlap its ops) three times, each
time under a host annotation ``work`` and followed by a 10 ms sleep
under ``idle-wait``, with the profiler's default options.

    python bench/testdata/record_trace.py
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "small.xplane.pb")


def main() -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    g = jax.jit(lambda a: (a * 2.0).sum())
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    f(a, b).block_until_ready()
    g(a).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.dirname(OUT))
    jax.profiler.start_trace(tmp)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("work"):
            f(a, b).block_until_ready()
            g(a).block_until_ready()
        with jax.profiler.TraceAnnotation("idle-wait"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(src[0], OUT)
    shutil.rmtree(tmp)
    print(OUT, os.path.getsize(OUT))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The controls of ``correct``, at a size a test run can hold: the plain
reference put in the program's place one precision step below the
configuration's fails the comparison."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import loader, tiny  # noqa: E402


def test_fp8_control_fails_the_serving_limit():
    """stablelm's reference at its depth (24 layers) and a width a CPU
    holds: where fp8 weights choose the token, its f32 logit lies further
    below the best than the limit allows."""
    import jax
    import jax.numpy as jnp

    cfg = loader.config("stablelm-1.6b")
    ref = loader.reference("stablelm-1.6b")
    model = dict(cfg["model"], hidden_size=256, intermediate_size=704,
                 vocab_size=8192, num_attention_heads=4,
                 num_key_value_heads=4)
    gaps = []
    for seed in range(3):
        params = jax.jit(lambda k: ref.init_params(k, model))(
            jax.random.key(seed))
        toks = jax.random.randint(jax.random.key(100 + seed), (2, 256), 0,
                                  model["vocab_size"])
        idx = jnp.broadcast_to(jnp.arange(256), (2, 256))
        f32 = ref.logits_at(params, toks, idx, model, "f32")
        fp8 = ref.logits_at(params, toks, idx, model, "fp8")
        chosen = jnp.take_along_axis(f32, fp8.argmax(-1)[..., None], -1)
        gaps.append(float(jnp.max(f32.max(-1) - chosen[..., 0])))
    assert min(gaps) > cfg["correct"]["max_logit_gap"], gaps


def test_bf16_control_fails_the_hfl_limits():
    """The paper's round in bf16 against the f32 reference, at the tiny
    HFL size: at least one number is over its limit."""
    import jax

    from bench.drivers import hfl
    from bench import generator, sensor_data

    cfg = tiny.hfl_config()
    ref = loader.reference("gru-traffic")
    run = cfg["run"]
    rng = generator.rng_for(5, 2)
    data = sensor_data.generate(tiny.HFL_TRAFFIC["data_days"], rng)
    sensors = sensor_data.select_sensors(data, run["clients_per_cluster"],
                                         rng)
    ids = np.repeat(np.arange(4), run["clients_per_cluster"])
    p = ref.init_params(jax.random.key(5), cfg["model"])
    p0 = jax.tree.map(lambda x: np.broadcast_to(
        np.asarray(x), (len(sensors),) + x.shape).copy(), p)
    z = (data["speeds"] - data["mean"]) / data["std"]
    want = ref.run_steps(p0, z, sensors, ids, run, 5, 3, 2)
    got = ref.run_steps(p0, z, sensors, ids, run, 5, 3, 2, mode="bf16")
    readings = hfl.compare(got[0], got[1].tolist(), p0, *want)
    over = [k for k, v in readings.items() if v > cfg["correct"][k]]
    assert over, readings

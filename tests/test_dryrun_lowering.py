"""Dry-run machinery smoke on a small host-device mesh (subprocess owns
its XLA device-count flag).  The full 512-device sweep lives in
repro.launch.dryrun; this proves the lowering path + roofline extraction
end-to-end in CI time."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from jax.sharding import AxisType
    from repro.configs import INPUT_SHAPES, get_config
    from repro.launch import shardings as sh
    from repro.launch.dryrun import build_programs
    from repro.launch.roofline import collective_stats, analyze, model_flops_for
    from repro.launch.analytic import analytic_roofline

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    cfg = get_config("xlstm-125m")
    rules = sh.rules_for(cfg, mesh)

    import dataclasses
    # shrink the shape for CI: 512 seq, batch 8
    shape = dataclasses.replace(INPUT_SHAPES["train_4k"], seq_len=512,
                                global_batch=8)
    import repro.launch.dryrun as dr
    import repro.configs as C
    C.INPUT_SHAPES["ci_train"] = shape
    dr.INPUT_SHAPES["ci_train"] = shape

    fn, inputs = dr.build_programs("xlstm-125m", "ci_train", mesh, rules)
    lowered = fn.lower(*inputs)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    assert float(cost.get("flops", 0)) > 0
    st = collective_stats(compiled.as_text())
    assert st.total_bytes > 0, "expected collectives on a sharded mesh"
    roof = analyze(compiled, mesh, model_flops_for(cfg, shape))
    assert roof.dominant in ("compute", "memory", "collective")
    ana = analytic_roofline(cfg, shape, mesh)
    assert ana.compute_s > 0 and ana.memory_s > 0
    print("DRYRUN_CI_OK", roof.dominant, f"{st.total_bytes:.3g}")
""")


def test_dryrun_lowering_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN_CI_OK" in out.stdout

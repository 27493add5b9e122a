"""chip_smoke.py on the CPU: it refuses to run without a TPU or outside
a checkout, and its phases pass at a reduced size (the chip runs them at
the published widths)."""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(script_dir: Path, *args, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=script_dir, env=env, capture_output=True,
                          text=True, timeout=300)


def _assert_refused(out):
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_refuses_without_a_tpu():
    out = _run(ROOT)
    _assert_refused(out)
    assert "no TPU found" in out.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(tmp_path)
    _assert_refused(out)
    assert "No module named 'repro'" in out.stderr


def test_serving_phases_reduced():
    with chip_smoke.CompileClock() as clock:
        chip_smoke.serving_phases(clock, reduced=True, n_requests=4,
                                  prompt_lens=(8, 40), new_tokens=4,
                                  slots=2, max_len=64)
    assert clock.compile_s() > 0


def test_compile_clock_counts_the_union_of_nested_spans():
    clock = chip_smoke.CompileClock()
    clock.spans = [(0.0, 10.0), (2.0, 3.0), (12.0, 15.0), (14.0, 16.0)]
    assert clock.compile_s() == 14.0
    assert clock.compile_s(since=5.0) == 9.0
    assert clock.compile_s(since=20.0) == 0.0


def test_serving_reference_check_catches_a_lost_cache_write():
    """A prefill that loses its keys passes its own logits but the decode
    after it misses the forward."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import make_model

    cfg = get_config("stablelm-1.6b").reduced()
    api = make_model(cfg)
    params = api.init_params(jax.random.key(0))[0]
    prompt = np.random.default_rng(0).integers(0, 1024, 24)
    chip_smoke.reference_check(api, params, prompt, max_len=64)

    def lossy_prefill(*args, **kwargs):
        logits, cache = api.prefill(*args, **kwargs)
        layers = cache["layers"]
        return logits, dict(cache, layers=layers._replace(
            k=jax.numpy.zeros_like(layers.k)))

    wrong = api._replace(prefill=lossy_prefill)
    with pytest.raises(chip_smoke.SmokeFailure, match="decode logits"):
        chip_smoke.reference_check(wrong, params, prompt, max_len=64)


def test_hfl_phase():
    from repro.configs import get_config

    chip_smoke.hfl_phase(get_config(chip_smoke.HFL_ARCH), rounds=2)


SHARDMAP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, sys.argv[1])
    import jax
    import chip_smoke
    from repro.configs import get_config
    # reversed: the mesh's device order need not be the caller's (on a
    # TPU it follows the chips' links)
    chip_smoke.hfl_shardmap_phase(get_config(chip_smoke.HFL_ARCH),
                                  jax.devices()[:4][::-1])
    print("SHARDMAP_PHASE_OK")
""")


def test_hfl_shardmap_phase_on_four_host_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDMAP, str(ROOT)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDMAP_PHASE_OK" in out.stdout
    assert "one per device" in out.stdout

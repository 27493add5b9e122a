"""Finds a cell's files by name: ``BENCHMARK.json`` at the root, the
configuration ``configs/<config>.json`` with its plain reference
``configs/<config>.py``, the traffic mix ``traffic/<traffic>.json``, the
driver ``drivers/<driver>.py`` and each per-layer metric's reader
``metrics/<metric>.py``."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

from bench.common import BENCH, ROOT


def manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def workload(name: str, root: Path = ROOT) -> dict:
    for w in manifest(root)["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str):
    return _module(BENCH / "configs" / f"{config_name}.py",
                   "bench_ref_" + config_name.replace("-", "_")
                   .replace(".", "_"))


def driver(name: str):
    return _module(BENCH / "drivers" / f"{name}.py", "bench_driver_" + name)


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_"))


def check_layout(params, arch) -> None:
    """Raise unless ``params`` have the tree, shapes and dtypes of the
    program's own ``init_params`` for ``arch``."""
    import jax

    from repro.models import make_model

    want = jax.eval_shape(lambda: make_model(arch).init_params(
        jax.random.key(0))[0])
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise ValueError("the reference's weights do not have the "
                         "program's layout")

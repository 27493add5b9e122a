"""The benchmark's manifest, its files found by name, its traffic
generator, and its refusal to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path
from statistics import NormalDist

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import generator, loader, metrics_table  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_and_metric_resolves_to_its_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    for c in configs.values():
        cfg = loader.config(c["name"])
        assert cfg["name"] == c["name"]
        assert (ROOT / c["file"]).is_file()
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert hasattr(loader.reference(c["name"]), "init_params")
        assert hasattr(loader.driver(cfg["driver"]), "run")
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs
        assert loader.traffic(w["traffic"])
        assert loader.workload(w["name"]) == w
        assert w["chips"] in (1, 4)
    for m in MANIFEST["per_layer"]:
        assert callable(loader.metric_reader(m["name"]).read)
        assert all(w in {x["name"] for x in MANIFEST["workloads"]}
                   for w in m["workloads"])
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert set(configs) == {w["config"] for w in MANIFEST["workloads"]}
    assert cells


def test_names_and_units_use_the_allowed_characters():
    names = [c["name"] for c in MANIFEST["configs"]]
    names += [w[k] for w in MANIFEST["workloads"]
              for k in ("name", "config", "traffic")]
    names += [k for c in MANIFEST["configs"] for k in c["reduced"]]
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in MANIFEST[key]]
        assert len(got) == len(set(got))
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for path in ROOT.joinpath("bench").rglob("*"):
        rel = path.relative_to(ROOT).as_posix()
        if "__pycache__" not in rel:
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    secs = MANIFEST["run_seconds"]
    assert isinstance(secs, int) and 1 <= secs <= 51
    # a full check of 24 cells must fit its allowance
    assert (2 + 14 * 24) * (secs + 60) + 24 * 2 * 90 + 1200 <= 43200
    texts = [x["why"] for k in ("configs", "workloads") for x in MANIFEST[k]]
    texts += [m["layer"] for m in MANIFEST["per_layer"]]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_traffic_is_the_same_work_for_every_seed():
    traffic = loader.traffic("conversation")
    a = generator.requests(traffic, 7, 51, 100352)
    b = generator.requests(traffic, 7, 51, 100352)
    c = generator.requests(traffic, 2 ** 33 + 7, 51, 100352)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    key = lambda rs: sorted(len(r.prompt) for r in rs)     # noqa: E731
    assert key(a) == key(c)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in c)
    gaps = lambda rs: np.sort(np.diff([0.0] + [r.due_s for r in rs]))  # noqa
    np.testing.assert_allclose(gaps(a), gaps(c), rtol=1e-9)
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert all(0 <= r.due_s < 51 for r in a)
    lens = np.asarray([len(r.prompt) for r in a])
    assert lens.min() >= 64 and lens.max() <= 1792
    # truncated, not clipped: no pile of requests at the bounds, and the
    # median is the truncated lognormal's
    assert np.sum(lens == 1792) <= 1 and np.sum(lens == 64) <= 1
    spec, nd = traffic["prompt_tokens"], NormalDist()
    lo, hi = (nd.cdf(np.log(spec[k] / spec["median"]) / spec["sigma"])
              for k in ("min", "max"))
    want = spec["median"] * np.exp(spec["sigma"] * nd.inv_cdf((lo + hi) / 2))
    assert abs(np.median(lens) - want) <= 0.03 * want


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "hfl-paper", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "no TPU" in out.stderr


def test_a_new_cell_is_new_files_and_manifest_entries(tmp_path):
    """A later PR adds a traffic file and a workload entry and edits no
    file: the copy's loader resolves the new cell and its metrics."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = loader.traffic("continual-rounds")
    traffic["rounds_per_call"] = 4
    (tmp_path / "bench" / "traffic" / "continual-rounds-4.json").write_text(
        json.dumps(traffic))
    manifest["workloads"].append(
        {"name": "hfl-paper-4", "config": "gru-traffic",
         "traffic": "continual-rounds-4", "chips": 1, "why": "test"})
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        if "hfl-paper" in m.get("workloads", []):
            m["workloads"].append("hfl-paper-4")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    probe = (
        "import sys; sys.path.insert(0, '.')\n"
        "from bench import loader, metrics_table\n"
        "w = loader.workload('hfl-paper-4')\n"
        "assert loader.traffic(w['traffic'])['rounds_per_call'] == 4\n"
        "assert loader.config(w['config'])['driver'] == 'hfl'\n"
        "m = loader.manifest()\n"
        "names = [x['name'] for x in m['per_layer']\n"
        "         if metrics_table.applies(x, w, m)]\n"
        "assert 'hfl_mfu' in names and 'round_ms' not in names\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "ok", out.stderr
    assert metrics_table.applies(MANIFEST["per_layer"][0],
                                 {"name": "hfl-paper"}, MANIFEST)

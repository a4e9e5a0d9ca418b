"""BENCHMARK.json drives the harness: every name resolves to its own file,
an unknown name is an error, and a cell, configuration, traffic mix or
metric is added with new files alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import registry

BENCH = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_in_the_benchmark_resolves():
    for c in BENCH["configs"]:
        cfg = registry.config(c["name"])
        registry.reference(cfg["reference"])
    for w in BENCH["workloads"]:
        cell = registry.cell(w["name"])
        mix = registry.traffic(cell["traffic"])
        registry.generator(mix["generator"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


@pytest.mark.parametrize(
    "lookup",
    [
        registry.cell,
        registry.config,
        registry.traffic,
        registry.generator,
        registry.reference,
        registry.metric_reader,
    ],
)
def test_an_unknown_name_is_an_error(lookup):
    with pytest.raises(KeyError):
        lookup("no_such_name")


def test_the_benchmark_keeps_its_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        reported = [m for m in BENCH["end_to_end"] if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
        assert any(w["name"] in m.get("workloads", cells) for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in moved.get("workloads", cells), (m["name"], c)
    for c in BENCH["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert os.path.isfile(os.path.join(registry.ROOT, c["file"]))


def test_new_files_add_a_cell_without_editing_an_existing_file(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(registry.HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = {
        p: (root / "chipbench" / p).read_bytes()
        for p in ("configs/job2_airline_20n400kg.json", "traffic/steady.json")
    }
    cfg = json.loads(before["configs/job2_airline_20n400kg.json"])
    cfg["nodes"] = 10
    (root / "chipbench/configs/job2_airline_10n400kg.json").write_text(json.dumps(cfg))
    mix = json.loads(before["traffic/steady.json"])
    mix["warmup_batches"] = [[32768, 8]]
    (root / "chipbench/traffic/half.json").write_text(json.dumps(mix))
    (root / "chipbench/metrics/ticks_in_window.py").write_text(
        "def read(run):\n    return run.window_ticks\n"
    )
    bench["configs"].append(dict(bench["configs"][-1], name="job2_airline_10n400kg",
                                 file="chipbench/configs/job2_airline_10n400kg.json"))
    bench["workloads"].append({"name": "job2.half", "config": "job2_airline_10n400kg",
                               "traffic": "half", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "ticks_in_window", "unit": "ticks", "better": "higher",
                               "source": "host_clock", "layer": "engine tick",
                               "moves": "events_per_s", "workloads": ["job2.half"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    probe = (
        "from chipbench import registry as r\n"
        "c = r.cell('job2.half'); cfg = r.config(c['config']); mix = r.traffic(c['traffic'])\n"
        "assert cfg['nodes'] == 10 and mix['warmup_batches'] == [[32768, 8]]\n"
        "names = [m['name'] for m in r.cell_metrics('job2.half', 'per_layer')]\n"
        "assert 'ticks_in_window' in names, names\n"
        "class Run: window_ticks = 7\n"
        "assert r.metric_reader('ticks_in_window')(Run()) == 7\n"
    )
    proc = subprocess.run([sys.executable, "-c", probe], cwd=root, capture_output=True,
                          text=True, timeout=120, env=dict(os.environ, PYTHONPATH=str(root)))
    assert proc.returncode == 0, proc.stderr
    for p, data in before.items():
        assert (root / "chipbench" / p).read_bytes() == data

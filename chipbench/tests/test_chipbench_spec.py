"""Lookup of configurations, mixes, limits and metric readers by the names
in BENCHMARK.json; an unknown name is refused; a new cell and metric are
new files plus new entries, with no existing file edited."""

import json
import os
import shutil

import pytest

import chipbench_tiny
from chipbench import spec


def test_every_cell_resolves():
    b = spec.load_benchmark()
    for w in b["workloads"]:
        c = spec.cell(b, w["name"])
        assert c["config"]["name"] == w["config"]
        assert c["traffic"]["name"] == w["traffic"]
        assert c["limits"] and all(v["limit"] > 0 for v in c["limits"].values())
        for traced in (False, True):
            for m in spec.metrics(b, w["name"], traced):
                assert callable(spec.reader(m["name"]))
        names = {m["name"] for m in spec.metrics(b, w["name"], False)}
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics(b, w["name"], True)
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12


@pytest.mark.parametrize("what", ["workload", "config", "traffic", "metric",
                                  "peaks"])
def test_unknown_names_are_refused(what):
    b = spec.load_benchmark()
    with pytest.raises(LookupError):
        if what == "workload":
            spec.cell(b, "no-such.cell")
        elif what == "config":
            b["workloads"].append({"name": "x.chat", "config": "nope",
                                   "traffic": "chat", "chips": 1, "why": "-"})
            spec.cell(b, "x.chat")
        elif what == "traffic":
            w = dict(b["workloads"][0], name="y", traffic="nope")
            b["workloads"].append(w)
            spec.cell(b, "y")
        elif what == "metric":
            spec.reader("no_such_metric")
        else:
            spec.peaks("TPU v1")


def test_a_new_cell_and_metric_are_new_files_only(tmp_path):
    root = chipbench_tiny.ROOT
    shutil.copytree(os.path.join(root, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in tmp_path.rglob("*") if x.is_file())}
    cb = tmp_path / "chipbench"
    cfg = dict(chipbench_tiny.CONFIG)
    (cb / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "tinychat.json").write_text(json.dumps(chipbench_tiny.MIX))
    (cb / "limits" / "tiny.tinychat.json").write_text(
        json.dumps({"worst_gap_sd": {"limit": chipbench_tiny.LIMIT}}))
    (cb / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.requests))\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny.tinychat", "config": "tiny",
                           "traffic": "tinychat", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "requests_seen", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "client driver and RPC plane",
                           "moves": "tokens_per_s"})
    c = spec.cell(b, "tiny.tinychat", checkout=str(tmp_path))
    assert c["config"] == cfg and c["traffic"]["clients"] == 3
    per_layer = [m["name"] for m in spec.metrics(b, "tiny.tinychat", True)]
    assert "requests_seen" in per_layer and "kv_copy_ms" not in per_layer
    assert spec.reader("requests_seen", str(tmp_path))(
        type("R", (), {"requests": [1, 2]})()) == 2.0
    ref, prog = spec.family(c["config"], str(tmp_path))
    assert hasattr(ref, "reference_compare") and hasattr(prog, "program_config")
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"

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


#: a second family's two files: llama's weights and reference, with counts
#: of its own (each a plain multiple, so a reading shows whose it is)
TWIN = '''
from chipbench import spec

_llama = spec.family({"family": "llama"})[0]
plan, make_shards = _llama.plan, _llama.make_shards
reference_compare, control_logits = (_llama.reference_compare,
                                     _llama.control_logits)


def prefill_flops(c, prompt_len):
    return 1000 * prompt_len


def decode_flops(c, pos):
    return 10 * (pos + 1)


def fused_step(c, n_layers, first, last, lengths, param_bytes):
    return {"flops": 7 * sum(lengths), "bytes": 11 * n_layers * len(lengths)}
'''
TWIN_PROGRAM = '''
from chipbench import spec

program_config = spec.family({"family": "llama"})[1].program_config
'''


def test_a_second_family_brings_its_own_counts(tmp_path):
    """A family found by its file name alone sets the fused calls' counts
    and ``mfu``; no file of the harness knows it."""
    import time

    from chipbench import serve

    models = tmp_path / "chipbench" / "models"
    models.mkdir(parents=True)
    (models / "twin.py").write_text(TWIN)
    (models / "twin_program.py").write_text(TWIN_PROGRAM)
    config = dict(chipbench_tiny.CONFIG, name="tiny-twin", family="twin")
    family, program = spec.family(config, str(tmp_path))
    out = serve.drive(config, chipbench_tiny.MIX, seed=21, seconds=0.5,
                      traced=True, chips=1, peaks=chipbench_tiny.PEAKS,
                      t_start=time.perf_counter(), family=family,
                      program=program, log=lambda msg: None)
    run = out["run"]
    assert run.family is family
    calls = run.calls["fused"]
    assert calls
    for c in calls:
        assert c.info["flops"] == 7 * sum(c.info["lengths"])
        assert c.info["bytes"] == 11 * c.info["n_layers"] * len(c.info["lengths"])
    total = sum(1000 * r.prompt_len if i == 0 else 10 * (r.prompt_len + i)
                for r in run.requests for i, t in enumerate(r.stamps)
                if run.in_window(t))
    assert total > 0
    want = 100.0 * total / (run.window_s * chipbench_tiny.PEAKS["bf16_flops"])
    assert spec.reader("mfu")(run) == pytest.approx(want, rel=1e-12)


def test_every_block_sends_each_length_of_the_mix():
    from chipbench import traffic as tf

    mix = spec.load_json(os.path.join(chipbench_tiny.ROOT, "chipbench",
                                      "traffic", "docqa.json"))
    size = len(tf.block(mix)[0])
    sent = {}
    for seed in (1, 2 ** 31 + 5, 2 ** 33 + 7):
        gen = tf.Traffic(mix, 1000, seed)
        reqs = [gen.next() for _ in range(3 * size)]
        sent[seed] = [(len(p), n) for p, n in reqs]
        for b in range(3):
            got = sent[seed][b * size:(b + 1) * size]
            assert sorted(p for p, _ in got) == sorted(tf.block(mix)[0])
            assert sorted(n for _, n in got) == sorted(tf.block(mix)[1])
    orders = list(sent.values())
    assert orders[0] != orders[1] and orders[1] != orders[2]

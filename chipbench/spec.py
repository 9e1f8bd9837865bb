"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file of its own, found by name, so a later cell, mix,
configuration or metric is a new file plus a new entry:

* ``configs[].file``                 the configuration, as it is run
* ``chipbench/models/<family>.py``   its weight maker, plain reference
                                     and counts (``prefill_flops``,
                                     ``decode_flops``, ``fused_step``)
* ``chipbench/models/<family>_program.py``  its system-side settings
* ``chipbench/traffic/<traffic>.json``  the mix's parameters
* ``chipbench/limits/<cell>.json``   the limit of each number compared
* ``chipbench/metrics/<metric>.py``  the reader of each metric
* ``chipbench/peaks.json``           peaks by ``device_kind``
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark(checkout: str = CHECKOUT) -> Dict[str, Any]:
    return load_json(os.path.join(checkout, "BENCHMARK.json"))


def entry(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise LookupError(f"unknown {what} {name!r}")
    return hits[0]


def _module(path: str, name: str) -> Any:
    """The module in file ``path``, imported once per process (as an
    import would be), so that its jitted functions keep their caches."""
    if not os.path.isfile(path):
        raise LookupError(f"no file {path} for {name!r}")
    key = "chipbench_file_" + "".join(
        ch if ch.isalnum() else "_" for ch in os.path.abspath(path))
    if key in sys.modules:
        return sys.modules[key]
    sp = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    sys.modules[key] = mod
    return mod


def cell(bench: Dict[str, Any], name: str,
         checkout: str = CHECKOUT) -> Dict[str, Any]:
    """The workload entry with its configuration, mix and limits loaded."""
    w = entry(bench["workloads"], name, "workload")
    c = entry(bench["configs"], w["config"], "config")
    here = os.path.join(checkout, "chipbench")
    config = load_json(os.path.join(checkout, c["file"]))
    if config["name"] != c["name"]:
        raise LookupError(f"{c['file']} holds {config['name']!r}, not {c['name']!r}")
    path = os.path.join(here, "traffic", w["traffic"] + ".json")
    if not os.path.isfile(path):
        raise LookupError(f"unknown traffic {w['traffic']!r}")
    return {"name": name, "chips": w["chips"], "config": config,
            "traffic": load_json(path),
            "limits": load_json(os.path.join(here, "limits", name + ".json"))}


def metrics(bench: Dict[str, Any], name: str, traced: bool) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics, or (traced) its per-layer ones: a
    metric with ``workloads`` goes to the cells it lists; one without
    goes to every cell that reports the metric it moves."""
    def mine(m: Dict[str, Any]) -> bool:
        return name in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def reader(metric: str, checkout: str = CHECKOUT) -> Callable[[Any], Optional[float]]:
    path = os.path.join(checkout, "chipbench", "metrics", metric + ".py")
    return _module(path, "metric_" + metric).read


def family(config: Dict[str, Any], checkout: str = CHECKOUT):
    """``(reference module, system-side module)`` of the config's family."""
    base = os.path.join(checkout, "chipbench", "models", config["family"])
    return (_module(base + ".py", config["family"]),
            _module(base + "_program.py", config["family"] + "_program"))


def peaks(kind: str, checkout: str = CHECKOUT) -> Dict[str, Any]:
    table = load_json(os.path.join(checkout, "chipbench", "peaks.json"))
    if kind not in table:
        raise LookupError(f"no peaks for device kind {kind!r}")
    return table[kind]

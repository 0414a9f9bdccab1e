"""The harness's own check of BENCHMARK.json and the files it names:
the contract's limits on names, units and lengths, and that every
entry finds its data file and agrees with it.

`python -m dssbench.schema` prints what is wrong and exits non-zero;
`python -m dssbench.schema --write` first rewrites BENCHMARK.json's
`per_layer` from dssbench/metrics/*.json (the files are where a metric
is defined; BENCHMARK.json repeats what the driver reads)."""

from __future__ import annotations

import glob
import json
import os
import re
import sys

from .deploy import REPO

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
PER_LAYER_KEYS = ("name", "unit", "better", "source", "layer", "moves",
                  "workloads")


def metric_files() -> list:
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path, encoding="utf-8") as fh:
            out.append(json.load(fh))
    return out


def per_layer_entries() -> list:
    return [{k: m[k] for k in PER_LAYER_KEYS if k in m}
            for m in metric_files()]


def problems(bench: dict) -> list:
    bad = []

    def name_ok(what, s):
        if not isinstance(s, str) or not NAME.match(s):
            bad.append(f"{what}: {s!r} is not a name")

    def line_ok(what, s):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 or re.search(
                r"[\n\t]", s):
            bad.append(f"{what}: not one line of 1-200 characters")

    if set(bench) != {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}:
        bad.append(f"top-level keys are {sorted(bench)}")
        return bad
    if not (isinstance(bench["run_seconds"], int)
            and 1 <= bench["run_seconds"] <= 51):
        bad.append("run_seconds is not a whole number from 1 to 51")
    for word in bench["command"]:
        line_ok("command", word)
    configs = {}
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config keys {sorted(c)}")
            continue
        name_ok("config", c["name"])
        line_ok(f"config {c['name']} source", c["source"])
        line_ok(f"config {c['name']} why", c["why"])
        configs[c["name"]] = c
        path = os.path.join(REPO, c["file"])
        if not any(c["file"].startswith(p + "/") for p in bench["paths"]):
            bad.append(f"{c['file']} is not under paths")
        if not os.path.isfile(path):
            bad.append(f"{c['file']} is missing")
            continue
        with open(path, encoding="utf-8") as fh:
            doc = json.load(fh)
        for key in c["reduced"]:
            name_ok("reduced", key)
            if not isinstance(doc.get(key), (int, float)):
                bad.append(f"{c['file']}: reduced key {key} is no number "
                           "at the top level")
            if key not in doc.get("published", {}):
                bad.append(f"{c['file']}: published.{key} is missing")
    cells = {}
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload keys {sorted(w)}")
            continue
        for k in ("name", "config", "traffic"):
            name_ok(f"workload {k}", w[k])
        line_ok(f"workload {w['name']} why", w["why"])
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips {w['chips']}")
        if w["config"] not in configs:
            bad.append(f"{w['name']}: no config {w['config']}")
        if not os.path.isfile(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json")):
            bad.append(f"{w['name']}: no traffic file {w['traffic']}")
        cells[w["name"]] = w
    e2e = {}
    for m in bench["end_to_end"]:
        if not set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}:
            bad.append(f"end_to_end keys {sorted(m)}")
            continue
        name_ok("end_to_end", m["name"])
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.25:
            bad.append(f"{m['name']}: bound {m['bound']}")
        if not os.path.isfile(os.path.join(HERE, "end_to_end",
                                           m["name"] + ".json")):
            bad.append(f"{m['name']}: no dssbench/end_to_end file")
        e2e[m["name"]] = m
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    files = {m["name"]: m for m in metric_files()}
    for m in bench["per_layer"]:
        if not set(m) <= set(PER_LAYER_KEYS):
            bad.append(f"per_layer keys {sorted(m)}")
            continue
        name_ok("per_layer", m["name"])
        line_ok(f"{m['name']} layer", m["layer"])
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source {m['source']!r}")
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves {m['moves']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: no workload {w}")
        f = files.get(m["name"])
        if f is None:
            bad.append(f"{m['name']}: no dssbench/metrics file")
        elif {k: f[k] for k in PER_LAYER_KEYS if k in f} != m:
            bad.append(f"{m['name']}: BENCHMARK.json and its file differ")
        elif not os.path.isfile(os.path.join(HERE, "readers",
                                             f["reader"] + ".py")):
            bad.append(f"{m['name']}: no reader {f['reader']}")
    for name in files:
        if name not in {m["name"] for m in bench["per_layer"]}:
            bad.append(f"dssbench/metrics/{name}.json is not in per_layer")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    if len(names) != len(set(names)):
        bad.append("two metrics share a name")
    return bad


def main() -> int:
    path = os.path.join(REPO, "BENCHMARK.json")
    with open(path, encoding="utf-8") as fh:
        bench = json.load(fh)
    if "--write" in sys.argv:
        bench["per_layer"] = per_layer_entries()
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(bench, fh, indent=1)
            fh.write("\n")
    bad = problems(bench)
    for b in bad:
        print(b, file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

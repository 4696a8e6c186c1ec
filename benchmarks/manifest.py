"""Loading ``BENCHMARK.json`` and the data files it points to, and the
check that refuses a manifest the driver would refuse, before a chip second
is spent (``python -m benchmarks.run --check``)."""

from __future__ import annotations

import importlib.util
import json
import os
import re

from benchmarks.common import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LAYERS = ("loadgen", "scheduler", "engine", "kv_pool", "model_step",
          "train_step", "input_pipeline", "kernels", "collectives", "device")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
HERE = os.path.dirname(os.path.abspath(__file__))


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_json(rel: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, rel)) as fh:
        return json.load(fh)


def cell_files(manifest: dict, cell_name: str):
    """(cell entry, config file dict, traffic dict, limits dict)."""
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == cell_name), None)
    if cell is None:
        raise SystemExit(f"no workload {cell_name!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(entry["file"])
    traffic = load_json(f"benchmarks/traffic/{cell['traffic']}.json")
    limits = load_json(f"benchmarks/cells/{cell_name}.json")
    return cell, config, traffic, limits


def load_reader(metric_name: str):
    """The per-layer metric's own file, found by the metric's name: a
    docstring and ``read(collected)``. Its layer, unit, source, arrow and
    cells stand in ``BENCHMARK.json`` alone, so that a later cell that
    reports the metric is one manifest entry and no edit here."""
    path = os.path.join(HERE, "layer_metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _line(s, what, problems, limit=200):
    if (not isinstance(s, str) or not 1 <= len(s) <= limit
            or "\n" in s or "\t" in s):
        problems.append(f"{what}: must be 1 to {limit} characters on one "
                        f"line with no tab")


def check(root: str = ROOT) -> list[str]:
    """Every problem found, each naming the offending key; empty = pass."""
    problems: list[str] = []
    m = load_manifest(root)

    def name(s, what):
        if not isinstance(s, str) or not NAME.match(s):
            problems.append(f"{what}: {s!r} is not one token of letters, "
                            f"digits, '_', '.', '-' (1 to 64)")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(m) != want:
        problems.append(f"BENCHMARK.json keys {sorted(m)} != {sorted(want)}")
        return problems
    for w in m["command"]:
        _line(w, f"command word {w!r}", problems)
    if not 1 <= int(m["run_seconds"]) <= 51:
        problems.append("run_seconds outside 1..51")
    for p in m["paths"]:
        if not os.path.isdir(os.path.join(root, p)):
            problems.append(f"paths: {p!r} is not a directory")
    configs = {}
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            problems.append(f"configs[{c.get('name')}]: keys {sorted(c)}")
        name(c["name"], "configs.name")
        _line(c["source"], f"configs[{c['name']}].source", problems)
        _line(c["why"], f"configs[{c['name']}].why", problems)
        if not any(c["file"].startswith(p + "/") for p in m["paths"]):
            problems.append(f"configs[{c['name']}].file outside paths")
        for k in c["reduced"]:
            name(k, f"configs[{c['name']}].reduced")
        try:
            f = load_json(c["file"], root)
        except (OSError, ValueError) as e:
            problems.append(f"configs[{c['name']}].file: {e}")
            continue
        configs[c["name"]] = f
        if f.get("source") != c["source"]:
            problems.append(f"{c['file']}: source differs from the manifest")
        if sorted(f.get("reduced", [])) != sorted(c["reduced"]):
            problems.append(f"{c['file']}: reduced differs from the manifest")
        for k in ("assumed", "departures", "deployment", "memory_reckoning",
                  "runner", "kind"):
            if k not in f:
                problems.append(f"{c['file']}: no {k!r}")
        _line(f.get("source", ""), f"{c['file']}.source", problems)
    cells = {}
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            problems.append(f"workloads[{w.get('name')}]: keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            name(w[k], f"workloads[{w.get('name')}].{k}")
        _line(w["why"], f"workloads[{w['name']}].why", problems)
        if w["chips"] not in (1, 4):
            problems.append(f"workloads[{w['name']}].chips not 1 or 4")
        if w["config"] not in configs:
            problems.append(f"workloads[{w['name']}].config unknown")
        for rel in (f"benchmarks/traffic/{w['traffic']}.json",
                    f"benchmarks/cells/{w['name']}.json"):
            try:
                f = load_json(rel, root)
            except (OSError, ValueError) as e:
                problems.append(f"{rel}: {e}")
                continue
            if rel.startswith("benchmarks/traffic/"):
                if f.get("name") != w["traffic"]:
                    problems.append(f"{rel}: name differs from its file")
                for k in ("who", "why", "kind"):
                    if k not in f:
                        problems.append(f"{rel}: no {k!r}")
            elif "limits" not in f:
                problems.append(f"{rel}: no 'limits'")
        cells[w["name"]] = w
    if len({(w["config"], w["traffic"]) for w in m["workloads"]}) != len(
            m["workloads"]):
        problems.append("workloads: a pair of config and traffic twice")
    four = [w["name"] for w in m["workloads"] if w["chips"] == 4]
    if len(four) > max(1, len(m["workloads"]) // 4):
        problems.append(f"workloads: {four} ask for 4 chips, too many")
    used = {w["config"] for w in m["workloads"]}
    for c in configs:
        if c not in used:
            problems.append(f"configs[{c}]: used by no cell")

    def cells_of(metric):
        return metric.get("workloads") or list(cells)

    e2e = {}
    for e in m["end_to_end"]:
        extra = set(e) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra:
            problems.append(f"end_to_end[{e.get('name')}]: keys {extra}")
        name(e["name"], "end_to_end.name")
        if not UNIT.match(e["unit"]):
            problems.append(f"end_to_end[{e['name']}].unit {e['unit']!r}")
        if e["better"] not in ("lower", "higher"):
            problems.append(f"end_to_end[{e['name']}].better")
        if e["source"] not in ("host_clock", "device_trace"):
            problems.append(f"end_to_end[{e['name']}].source")
        if not 0.01 <= float(e["bound"]) <= 0.1:
            problems.append(f"end_to_end[{e['name']}].bound outside 1%..10%")
        for w in e.get("workloads", []):
            if w not in cells:
                problems.append(f"end_to_end[{e['name']}]: no cell {w!r}")
        e2e[e["name"]] = e
    if "setup_s" not in e2e or "workloads" in e2e.get("setup_s", {}):
        problems.append("end_to_end: setup_s must be there, in every cell")
    for cname in cells:
        others = [e for e in e2e.values() if e["name"] != "setup_s"
                  and cname in cells_of(e)]
        if not others:
            problems.append(f"cell {cname}: no end-to-end metric but setup_s")
    seen = set(e2e)
    layer_cells = set()
    for p in m["per_layer"]:
        extra = set(p) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra:
            problems.append(f"per_layer[{p.get('name')}]: keys {extra}")
        name(p["name"], "per_layer.name")
        if p["name"] in seen:
            problems.append(f"per_layer[{p['name']}]: name used twice")
        seen.add(p["name"])
        name(p["layer"], f"per_layer[{p['name']}].layer")
        if p["layer"] not in LAYERS:
            problems.append(f"per_layer[{p['name']}].layer {p['layer']!r} "
                            f"is not one of {LAYERS}")
        if not UNIT.match(p["unit"]):
            problems.append(f"per_layer[{p['name']}].unit {p['unit']!r}")
        if p["better"] not in ("lower", "higher"):
            problems.append(f"per_layer[{p['name']}].better")
        if p["source"] not in SOURCES:
            problems.append(f"per_layer[{p['name']}].source")
        moved = e2e.get(p["moves"])
        if moved is None:
            problems.append(f"per_layer[{p['name']}].moves {p['moves']!r} "
                            f"is no end-to-end metric")
            continue
        for w in cells_of(p):
            if w not in cells:
                problems.append(f"per_layer[{p['name']}]: no cell {w!r}")
            elif w not in cells_of(moved):
                problems.append(
                    f"per_layer[{p['name']}]: cell {w} does not report "
                    f"{p['moves']}")
            layer_cells.add(w)
        try:
            mod = load_reader(p["name"])
        except (OSError, SyntaxError) as e:
            problems.append(f"layer_metrics/{p['name']}.py: {e}")
            continue
        if not callable(getattr(mod, "read", None)):
            problems.append(f"layer_metrics/{p['name']}.py: no read()")
    for cname in cells:
        if cname not in layer_cells:
            problems.append(f"cell {cname}: no per-layer metric")
    size = os.path.getsize(os.path.join(root, "BENCHMARK.json"))
    if size > 64 * 1024:
        problems.append("BENCHMARK.json over 64 KiB")
    for base, _, files in os.walk(os.path.join(root, "benchmarks")):
        if "__pycache__" in base:
            continue
        for fn in files:
            rel = os.path.relpath(os.path.join(base, fn), root)
            if not re.match(r"^[A-Za-z0-9_.\-/]+$", rel):
                problems.append(f"{rel}: file name outside the allowed set")
    return problems

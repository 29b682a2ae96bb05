"""BENCHMARK.json and the data files it names: loading, lookup, checking.

The harness holds no list of cells, configurations or metrics. Everything
is found by the name in ``BENCHMARK.json``:

* a configuration   -> its ``file`` (``benchmark/configs/<name>.json``)
* a traffic mix     -> ``benchmark/workloads/<traffic>.json``
* a per-layer metric -> ``benchmark/layer_metrics/<name>.json``, and the
  Python file its reader may name beside it
* a reference family -> ``benchmark/reference/<family>.py`` (the plain
  reference and the model's FLOPs; ``reference.family`` of a configuration)

so a later PR adds a cell, a configuration, a metric or an architecture with
new files and new entries, and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from typing import Any, Dict, List

from benchmark import flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = "benchmark"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
# a width may never be cut (the builder's contract)
WIDTH_RE = re.compile(
    r"((hidden|intermediate|latent|state|proj\w*|head|ffn|inner)_"
    r"(size|dim|width)$|_dim$|_rank$|^n_embd$|^n_inner$|^d_model$|^d_ff$|"
    r"expan|experts_per_tok)", re.I)
# what a full check may cost: 2 + 14 runs a cell, at the full 24 cells
MAX_CELLS, CHECK_BUDGET_S = 24, 43200


def max_run_seconds() -> int:
    runs = 2 + 14 * MAX_CELLS
    return int((CHECK_BUDGET_S - 1200 - MAX_CELLS * 180) // runs - 60)


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    return read_json(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    """One entry of ``workloads`` with its files read."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]    # benchmark/configs/<config>.json
    traffic: Dict[str, Any]   # benchmark/workloads/<traffic>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_path(root: str, traffic: str) -> str:
    return os.path.join(root, BENCH_DIR, "workloads", traffic + ".json")


def layer_metric_path(root: str, name: str) -> str:
    return os.path.join(root, BENCH_DIR, "layer_metrics", name + ".json")


def family_path(root: str, family: str) -> str:
    return os.path.join(root, BENCH_DIR, "reference", family + ".py")


def load_python(path: str) -> Any:
    """The Python file at ``path`` as a module of its own: how the harness
    runs code that is found by name (a reference family, a reader or a
    kernel's cost beside a per-layer metric)."""
    name = "benchmark_file_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(manifest: Dict[str, Any], name: str,
                 root: str = ROOT) -> Cell:
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=read_json(os.path.join(root, cfg["file"])),
        traffic=read_json(traffic_path(root, w["traffic"])),
        end_to_end=e2e,
        per_layer=[m for m in manifest["per_layer"]
                   if _applies(m, name) and m["moves"] in names])


def train_argv(cell: Cell, seed: int, root: str = ROOT) -> List[str]:
    """The ``train_dist`` command line of a cell: the configuration's YAML
    and overrides, the traffic mix's overrides, the cell's chips and the
    seed. What the harness adds for its own window comes after these."""
    prog = cell.config["program"]
    return ([os.path.join(root, prog["yaml"])] + list(prog["overrides"])
            + list(cell.traffic["overrides"])
            + [f"parallel.num_devices={cell.chips}", f"train.seed={seed}"])


def second_stack_depth(config: Dict[str, Any]) -> int:
    """The blocks of a stack that the program runs beside the decoder's (a
    tower in front of it), which a family's ``attention_blocks`` may
    describe beyond ``num_hidden_layers``: the configuration's own key that
    ``reference.second_stack_depth_key`` names, which ``program.equals`` has
    to tie to the program's resolved settings. 0 where nothing is named."""
    key = config.get("reference", {}).get("second_stack_depth_key")
    if not key:
        return 0
    depth = config.get(key)
    if (key not in config["program"]["equals"].values()
            or not isinstance(depth, int) or isinstance(depth, bool)
            or depth < 0):
        raise ValueError(
            f"reference.second_stack_depth_key names {key!r}: it has to be "
            "a key of the configuration's file that holds a whole number "
            "from 0 on, and a value of program.equals, so that the program "
            f"is held to it (the file has {key}={depth!r}, program.equals "
            f"ties {sorted(config['program']['equals'].values())})")
    return depth


# ---------------------------------------------------------------------------
# the manifest check (the contract's static rules, as far as a file shows)
# ---------------------------------------------------------------------------


def check_manifest(manifest: Dict[str, Any], root: str = ROOT) -> List[str]:
    """Every way in which the manifest or its files break the benchmark's
    contract; empty when it holds."""
    bad: List[str] = []
    if set(manifest) != TOP_KEYS:
        bad.append(f"top-level keys {sorted(manifest)} != {sorted(TOP_KEYS)}")
        return bad
    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16 or not all(PATH_RE.match(p) for p in paths):
        bad.append(f"paths {paths}")
    inside = lambda p: any(p == d or p.startswith(d + "/") for d in paths)
    cmd = manifest["command"]
    if not 1 <= len(cmd) <= 32:
        bad.append("command has no word or more than 32")
    for word in cmd:
        if word.startswith("/") or ".." in word.split("/"):
            bad.append(f"command word {word!r} leaves the repo")
        elif os.path.exists(os.path.join(root, word)) and not inside(word):
            bad.append(f"command names {word!r}, outside paths")
    rs = manifest["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= max_run_seconds():
        bad.append(f"run_seconds {rs!r} not a whole number in "
                   f"1..{max_run_seconds()}")
    for group, keys in ENTRY_KEYS.items():
        seen = set()
        for e in manifest[group]:
            extra = set(e) - keys - ({"workloads"} if group in (
                "end_to_end", "per_layer") else set())
            if extra or not keys <= set(e):
                bad.append(f"{group} entry {e.get('name')!r}: keys "
                           f"{sorted(e)}")
                continue
            if not NAME_RE.match(e["name"]):
                bad.append(f"{group} name {e['name']!r}")
            if e["name"] in seen:
                bad.append(f"{group} name {e['name']!r} twice")
            seen.add(e["name"])
            for k in ("why", "layer", "source"):
                v = e.get(k)
                if v is not None and not (1 <= len(v) <= 200
                                          and "\n" not in v
                                          and "\t" not in v):
                    bad.append(f"{group} {e['name']!r}: {k} is not one "
                               "line of 1 to 200 characters")
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in manifest[g]]
    if len(set(metric_names)) != len(metric_names):
        bad.append("two metrics share a name")
    # configurations
    files = [c["file"] for c in manifest["configs"]]
    if len(set(files)) != len(files):
        bad.append("two configurations share a file")
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        if c["name"] not in used:
            bad.append(f"configuration {c['name']!r} has no cell")
        if not inside(c["file"]) or not os.path.isfile(
                os.path.join(root, c["file"])):
            bad.append(f"configuration file {c['file']!r} missing or "
                       "outside paths")
            continue
        if len(c["reduced"]) > 16:
            bad.append(f"{c['name']}: more than 16 reduced keys")
        body = read_json(os.path.join(root, c["file"]))
        for k in c["reduced"]:
            if not NAME_RE.match(k) or WIDTH_RE.search(k):
                bad.append(f"{c['name']}: reduced key {k!r} is a width or "
                           "not a name")
            if k not in body or k not in body.get("reduced_from", {}):
                bad.append(f"{c['name']}: reduced key {k!r} is not in the "
                           "file and its reduced_from")
        if sorted(body.get("reduced_from", {})) != sorted(c["reduced"]):
            bad.append(f"{c['name']}: reduced {c['reduced']} != the file's "
                       f"reduced_from {sorted(body.get('reduced_from', {}))}")
        family = body.get("reference", {}).get("family", "")
        if not NAME_RE.match(family) or not os.path.isfile(
                family_path(root, family)):
            where = os.path.relpath(family_path(root, family), root)
            bad.append(f"{c['name']}: reference family {family!r} has no "
                       f"file {where}")
        try:
            second_stack_depth(body)
        except ValueError as e:
            bad.append(f"{c['name']}: {e}")
    # cells
    cells = manifest["workloads"]
    if not 2 <= len(cells) <= MAX_CELLS:
        bad.append(f"{len(cells)} cells, wanted 2 to {MAX_CELLS}")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    if len(set(pairs)) != len(pairs):
        bad.append("a pair of configuration and traffic appears twice")
    four = [w["name"] for w in cells if w["chips"] == 4]
    if len(four) > max(1, len(cells) // 4):
        bad.append(f"{len(four)} four-chip cells of {len(cells)}: over 25%")
    cfg_names = {c["name"] for c in manifest["configs"]}
    for w in cells:
        if w["chips"] not in (1, 4):
            bad.append(f"{w['name']}: chips {w['chips']}")
        if w["config"] not in cfg_names:
            bad.append(f"{w['name']}: unknown configuration {w['config']!r}")
        if not NAME_RE.match(w["traffic"]) or not os.path.isfile(
                traffic_path(root, w["traffic"])):
            bad.append(f"{w['name']}: no traffic file for {w['traffic']!r}")
    # metrics
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    cell_names = {w["name"] for w in cells}
    for group in ("end_to_end", "per_layer"):
        if not 1 <= len(manifest[group]) <= (16 if group == "end_to_end"
                                             else 128):
            bad.append(f"{group}: {len(manifest[group])} metrics")
        for m in manifest[group]:
            if not UNIT_RE.match(m.get("unit", "")):
                bad.append(f"{m['name']}: unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                bad.append(f"{m['name']}: better {m.get('better')!r}")
            if m.get("source") not in SOURCES:
                bad.append(f"{m['name']}: source {m.get('source')!r}")
            if not set(m.get("workloads", cell_names)) <= cell_names:
                bad.append(f"{m['name']}: lists an unknown cell")
    for m in manifest["end_to_end"]:
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric is taken by the "
                       "benchmark itself (host_clock or device_trace)")
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.1:
            bad.append(f"{m['name']}: bound {b!r} outside 0.01..0.1")
    for m in manifest["per_layer"]:
        if m.get("moves") not in e2e:
            bad.append(f"{m['name']}: moves {m.get('moves')!r}, not an "
                       "end-to-end metric")
        path = layer_metric_path(root, m["name"])
        if not os.path.isfile(path):
            bad.append(f"{m['name']}: no reader file in "
                       f"{BENCH_DIR}/layer_metrics")
            continue
        reader = read_json(path)["reader"]
        beside = reader.get("file")
        if beside and not os.path.isfile(
                os.path.join(os.path.dirname(path), beside)):
            bad.append(f"{m['name']}: its reader names {beside!r}, which is "
                       f"not beside it in {BENCH_DIR}/layer_metrics")
        if reader["kind"] == "roofline" and ("pattern" in reader) == (
                "scopes" in reader):
            bad.append(f"{m['name']}: a roofline reader names a pattern or "
                       "scopes, one of the two")
        if (reader["kind"] == "roofline" and not beside
                and not callable(getattr(flops, reader["cost"], None))):
            bad.append(f"{m['name']}: cost {reader['cost']!r} is no function "
                       f"of {BENCH_DIR}/flops.py and the reader names no "
                       "file beside it")
    for w in cells:   # every cell: setup_s, another end-to-end, a per-layer
        mine = [m["name"] for m in manifest["end_to_end"]
                if _applies(m, w["name"])]
        if "setup_s" not in mine or len(mine) < 2:
            bad.append(f"{w['name']}: needs setup_s and one more end-to-end "
                       "metric")
        if not any(_applies(m, w["name"]) and m.get("moves") in mine
                   for m in manifest["per_layer"]):
            bad.append(f"{w['name']}: no per-layer metric")
    if len(json.dumps(manifest)) > 64 * 1024:
        bad.append("BENCHMARK.json is over 64 KiB")
    return bad

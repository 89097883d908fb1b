"""BENCHMARK.json and the benchmark's data files: loading, the driver's naming
rules, and finding a cell's files by name.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric sits in a file of its own; a later PR adds files and BENCHMARK.json
entries and edits nothing that is here.
"""

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """A name, unit or entry the driver would refuse."""


def check_name(name, what="name"):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(
            f"{what} {name!r}: 1 to 64 of a-z A-Z 0-9 _ . - and no leading "
            f". or -")
    return name


def check_unit(unit, what="unit"):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(
            f"{what} {unit!r}: 1 to 16 of a-z A-Z 0-9 _ / % . - and no space")
    return unit


def check_metric(entry, per_layer):
    check_name(entry.get("name"), "metric name")
    check_unit(entry.get("unit"), f"unit of {entry.get('name')}")
    if entry.get("better") not in ("lower", "higher"):
        raise SpecError(f"metric {entry['name']}: better must be lower|higher")
    if entry.get("source") not in SOURCES:
        raise SpecError(f"metric {entry['name']}: source must be one of "
                        f"{SOURCES}")
    if not per_layer and entry["source"] not in ("host_clock",
                                                 "device_trace"):
        raise SpecError(f"end-to-end metric {entry['name']}: the benchmark "
                        f"takes it itself, so host_clock or device_trace")
    for w in entry.get("workloads", ()):
        check_name(w, f"workload of metric {entry['name']}")


def load_benchmark(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for group, key in (("configs", "name"), ("workloads", "name"),
                       ("end_to_end", "name"), ("per_layer", "name")):
        names = [check_name(e.get(key), f"{group} name")
                 for e in bench[group]]
        if len(set(names)) != len(names):
            raise SpecError(f"{group}: a name appears twice")
    for w in bench["workloads"]:
        check_name(w["config"], "config")
        check_name(w["traffic"], "traffic")
    for m in bench["end_to_end"]:
        check_metric(m, per_layer=False)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        check_metric(m, per_layer=True)
        if m.get("moves") not in e2e:
            raise SpecError(f"per-layer metric {m['name']} moves "
                            f"{m.get('moves')!r}, which is no end-to-end "
                            f"metric")
    return bench


def find_workload(bench, name):
    """(workload entry, configuration entry) of the cell called `name`."""
    for w in bench["workloads"]:
        if w["name"] == name:
            for c in bench["configs"]:
                if c["name"] == w["config"]:
                    return w, c
            raise SpecError(f"workload {name}: no configuration "
                            f"{w['config']!r}")
    raise SpecError(f"no workload {name!r} in BENCHMARK.json (has "
                    f"{[w['name'] for w in bench['workloads']]})")


def metrics_for(bench, group, workload):
    """The metrics of `group` that exist in `workload`."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_config(root, config_entry):
    with open(os.path.join(root, config_entry["file"])) as f:
        return json.load(f)


def load_traffic(traffic, here=HERE):
    path = os.path.join(here, "traffic", check_name(traffic) + ".json")
    with open(path) as f:
        return json.load(f)


def load_module(kind, name, here=HERE):
    """benchmark/<kind>/<name>.py as a module, found by file name: a builder,
    a generator or a per-layer metric reader."""
    path = os.path.join(here, kind, check_name(name) + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no benchmark/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cut_for(config, chips):
    """The cut of a configuration that runs on `chips` chips."""
    cuts = config["cuts"]
    if str(chips) not in cuts:
        raise SpecError(f"configuration {config.get('name')}: no cut for "
                        f"{chips} chip(s), has {sorted(cuts)}")
    return cuts[str(chips)]

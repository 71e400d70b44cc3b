"""Finding a cell's pieces by name, and refusing malformed names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own; ``BENCHMARK.json`` names
them. A later PR adds a cell by adding files and entries:

    perfbench/configs/<config>.json          (the entry's ``file``)
    perfbench/traffic/<traffic>.json
    perfbench/layer_metrics/<metric>.json    -> readers/<reader>.py
                                             (<metric> less its last
                                             ".suffix" serves too)
    perfbench/runners/<runner>.py            (the config's ``runner``)
    perfbench/reference/<family>.py, perfbench/flops/<family>.py
"""

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def check_name(name, what="name"):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError("%s %r: letters, digits, '_', '.', '-' only, at "
                        "most 64, not starting with '.' or '-'"
                        % (what, name))
    return name


def check_unit(unit):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError("unit %r: 1 to 16 of letters, digits, '_', '/', "
                        "'%%', '.', '-'" % (unit,))
    return unit


def read_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return validate(read_json(os.path.join(root, "BENCHMARK.json")))


def _unique(entries, what):
    names = [check_name(e["name"], what) for e in entries]
    dup = {n for n in names if names.count(n) > 1}
    if dup:
        raise SpecError("duplicate %s: %s" % (what, sorted(dup)))
    return names


def validate(bench):
    """The name, unit and cross-reference rules a harness can check
    (the driver checks the rest of the contract)."""
    configs = _unique(bench["configs"], "configuration")
    cells = _unique(bench["workloads"], "cell")
    for w in bench["workloads"]:
        check_name(w["traffic"], "traffic")
        if w["config"] not in configs:
            raise SpecError("cell %s names no configuration: %r"
                            % (w["name"], w["config"]))
        if w["chips"] not in (1, 4):
            raise SpecError("cell %s: chips must be 1 or 4" % w["name"])
    e2e = _unique(bench["end_to_end"], "end-to-end metric")
    _unique(bench["end_to_end"] + bench["per_layer"], "metric")
    for m in bench["end_to_end"] + bench["per_layer"]:
        check_unit(m["unit"])
        if m["better"] not in ("lower", "higher"):
            raise SpecError("metric %s: better is lower or higher"
                            % m["name"])
        if m["source"] not in SOURCES:
            raise SpecError("metric %s: unknown source %r"
                            % (m["name"], m["source"]))
        for c in m.get("workloads", ()):
            if c not in cells:
                raise SpecError("metric %s lists no cell %r"
                                % (m["name"], c))
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            raise SpecError("metric %s moves no end-to-end metric: %r"
                            % (m["name"], m["moves"]))
    if "setup_s" not in e2e:
        raise SpecError("setup_s must be an end-to-end metric")
    return bench


def cell(bench, name, root=ROOT):
    """(workload entry, configuration file, traffic file) of a cell."""
    check_name(name, "cell")
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SpecError("no cell %r in BENCHMARK.json (have %s)"
                        % (name, [w["name"] for w in bench["workloads"]]))
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = read_json(os.path.join(root, entry["file"]))
    traffic = read_json(os.path.join(root, "perfbench", "traffic",
                                     w["traffic"] + ".json"))
    return w, config, traffic


def metrics_of(bench, kind, cell_name, reported=None):
    """The ``end_to_end`` or ``per_layer`` entries a cell reports. A
    metric with a ``workloads`` key belongs to those cells; a per-layer
    metric without one to every cell that reports what it ``moves``."""
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in (reported or ()):
            out.append(m)
    return out


def layer_metric(name, root=ROOT):
    """A per-layer metric's reader and its arguments, from the metric's
    own file ``layer_metrics/<name>.json`` (``reader``, ``args``; the
    rest of the metric is its ``BENCHMARK.json`` entry). A quantity
    split by cell kind shares one file: ``engine_step_ms.serve`` and
    ``engine_step_ms.batch`` are read by ``engine_step_ms.json``. A
    root other than the checkout (the tests' benchmark of added files)
    may lean on the committed metrics."""
    check_name(name, "metric")
    for base in (root, ROOT):
        for stem in (name, name.rpartition(".")[0]):
            path = os.path.join(base, "perfbench", "layer_metrics",
                                stem + ".json")
            if stem and os.path.exists(path):
                meta = read_json(path)
                reader = importlib.import_module(
                    "perfbench.layer_metrics.readers." + meta["reader"])
                return meta.get("args", {}), reader.read
    raise SpecError("per-layer metric %r has no file under "
                    "perfbench/layer_metrics" % (name,))


def runner(config):
    return importlib.import_module("perfbench.runners."
                                   + check_name(config["runner"]))


def family(config, package):
    """``perfbench.reference.<family>`` or ``perfbench.flops.<family>``."""
    return importlib.import_module("perfbench.%s.%s"
                                   % (package, check_name(config["family"])))


def peaks(device_kind, root=ROOT):
    table = read_json(os.path.join(root, "perfbench", "peaks.json"))
    if device_kind not in table:
        raise SpecError("device kind %r is not in perfbench/peaks.json "
                        "(have %s): add its published peaks with their "
                        "source" % (device_kind, sorted(table)))
    return table[device_kind]

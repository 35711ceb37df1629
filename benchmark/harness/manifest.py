"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A cell is an entry of ``workloads``; its configuration is the ``file`` of
the ``configs`` entry it names; its traffic mix is
``benchmark/traffic/<traffic>.json``; a per-layer metric is
``benchmark/metrics/<name>.json`` naming a reader module
``benchmark/readers/<reader>.py``; a configuration names a job module
``benchmark/jobs/<job>.py``. An unknown name is an error that lists what
exists.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class UnknownName(LookupError):
    pass


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _named(kind, name, known):
    if name not in known:
        raise UnknownName(
            f"unknown {kind} {name!r}; known: {', '.join(sorted(known))}")
    return known[name]


def _files(directory, suffix):
    d = os.path.join(HERE, directory)
    return {f[:-len(suffix)]: os.path.join(d, f)
            for f in os.listdir(d) if f.endswith(suffix)
            and not f.startswith("_")}


def manifest(root=ROOT):
    return _load(os.path.join(root, "BENCHMARK.json"))


def cell(man, name):
    return _named("workload", name, {w["name"]: w for w in man["workloads"]})


def config(man, name, root=ROOT):
    entry = _named("configuration", name,
                   {c["name"]: c for c in man["configs"]})
    return _load(os.path.join(root, entry["file"]))


def traffic(name):
    return _load(_named("traffic mix", name, _files("traffic", ".json")))


def metric_spec(name):
    return _load(_named("per-layer metric", name, _files("metrics", ".json")))


def job(name):
    _named("job module", name, _files("jobs", ".py"))
    return importlib.import_module(f"benchmark.jobs.{name}")


def reader(name):
    _named("reader", name, _files("readers", ".py"))
    return importlib.import_module(f"benchmark.readers.{name}")


def peak(device_kind):
    """Peak bytes/s of ``device_kind``; an unknown kind is an error."""
    table = _load(os.path.join(HERE, "peaks.json"))["devices"]
    return _named("device kind", device_kind, table)


def metrics_of(man, cell_name, which):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in man[which]
            if cell_name in m.get("workloads", [cell_name])]

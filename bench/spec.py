"""Finds a cell's parts by name: ``BENCHMARK.json`` at the checkout root,
``configs/<config>.json`` (the entry's ``file``), ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and ``forms/<form>.py`` (the config's ``form``).
Adding a cell, configuration, traffic mix, metric or architecture adds
files and entries; nothing here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
import zlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: dict  # metric name -> its BENCHMARK.json entry
    per_layer: dict


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: dict | None = None,
         root: pathlib.Path = ROOT) -> Cell:
    """The cell called ``name``; KeyError when BENCHMARK.json has none."""
    bench = bench if bench is not None else load_benchmark(root)
    wl = {w["name"]: w for w in bench["workloads"]}[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(wl["chips"]), config=config, traffic=traffic,
        end_to_end={m["name"]: m for m in bench["end_to_end"]
                    if _reports(m, name)},
        per_layer={m["name"]: m for m in bench["per_layer"]
                   if _reports(m, name)})


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(run) -> float | None`` of ``metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def form(name: str, root: pathlib.Path = ROOT):
    """The architecture module ``forms/<name>.py`` that a config names
    under ``form``, executed once a process; ValueError, naming the
    form, when there is none."""
    path = root / "bench" / "forms" / f"{name}.py"
    if not (NAME.match(name) and path.is_file()):
        raise ValueError(f"form {name!r}: no bench/forms/{name}.py")
    mod_name = "bench_form_{}_{:08x}".format(
        re.sub(r"\W", "_", name), zlib.crc32(str(path.resolve()).encode()))
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod  # the form's functions look it up
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return sys.modules[mod_name]


def peak(device_kind: str) -> dict:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (has {sorted(table)})")
    return table[device_kind]

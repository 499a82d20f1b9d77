"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Everything that belongs to one of them sits in a file of its own,
found by name under ``benchmarks/tpu/``:

  configs/<config>.json      the configuration as it is run (the entry's
                             ``file``); its ``reference`` key names the
                             plain reference in ``reference/``
  traffic/<traffic>.json     the traffic mix or calibration job: its
                             ``job`` key names the driver in ``jobs/``
  workloads/<cell>.json      the cell's own settings (rate, engine sizes,
                             the limits of the correctness comparison)
  metrics/<metric>.py        a per-layer reader (``metrics/<base>.py``,
                             ``base`` being the name before its first dot,
                             serves every cell suffix that has no file of
                             its own)

Adding a configuration, a traffic mix, a cell or a per-layer metric is
therefore a matter of new files and new entries; this module never
changes for one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
REL = HERE.relative_to(ROOT)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    root: Path
    entry: dict          # the BENCHMARK.json workload entry
    config_entry: dict   # the BENCHMARK.json configuration entry
    config: dict         # configs/<config>.json
    traffic: dict        # traffic/<traffic>.json
    params: dict         # workloads/<cell>.json
    end_to_end: tuple    # metric entries this cell reports (trace 0)
    per_layer: tuple     # metric entries this cell reports (trace 1)

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    @property
    def job(self) -> str:
        return self.traffic["job"]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[entry["config"]]
    base = root / REL
    return Cell(
        name=name, root=root, entry=entry, config_entry=cfg_entry,
        config=_load_json(root / cfg_entry["file"]),
        traffic=_load_json(base / "traffic" / f"{entry['traffic']}.json"),
        params=_load_json(base / "workloads" / f"{name}.json"),
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)))


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path (metric readers and
    drivers carry dots in their names, so they are not packages)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(cell: Cell, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``, else of
    ``metrics/<base>.py``."""
    d = cell.root / REL / "metrics"
    for stem in (metric, metric.split(".")[0]):
        path = d / f"{stem}.py"
        if path.exists():
            return load_module(path, f"bench_metric_{stem}").read
    raise FileNotFoundError(f"no reader for metric {metric!r} in {d}")


def job_module(cell: Cell):
    return load_module(cell.root / REL / "jobs" / f"{cell.job}.py",
                       f"bench_job_{cell.job}")


def reference_module(cell: Cell):
    ref = cell.config["reference"]
    return load_module(cell.root / REL / "reference" / f"{ref}.py",
                       f"bench_ref_{ref}")

"""BENCHMARK.json and the data files it names.

A cell names a configuration and a traffic mix; both are files found by
that name. A per-layer metric's reader is a file found by the metric's name;
its unit, layer, cells and what it moves are BENCHMARK.json's alone. What
the harness has to know of an architecture (its plain reference, its weight
bytes, its work counts) is a module found by the `model_type` that the
configuration's file states: `architectures/<model_type>.py`. No other part
of the harness knows the name of a cell, a configuration, a mix, a metric
or an architecture.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class ManifestError(Exception):
    pass


def _read(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e


def _merge(base: dict, over: dict) -> dict:
    """`over` laid on `base`, nested groups merged key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict  # the configuration's file, as run
    traffic: dict  # the mix's parameters
    limits: dict  # limits/<configuration>.json: what `correct` holds each number to
    arch: ModuleType  # architectures/<the configuration's model_type>.py
    end_to_end: list[dict]  # the manifest's entries this cell reports
    per_layer: list[dict]  # manifest entry merged with the metric's file


def applies(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def metric_file(bench_dir: Path, name: str) -> Path:
    """`metrics/<name>.json`, or the file of the longest dotted prefix of
    the name: `device.idle_share.fresh` reads as `device.idle_share` does,
    and differs from it only in BENCHMARK.json (its cells, what it moves)."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = bench_dir / "metrics" / (".".join(parts[:n]) + ".json")
        if path.exists():
            return path
    raise ManifestError(f"metric {name}: no file under {bench_dir / 'metrics'}")


ARCH_GIVES = ("make_weights", "logits_for", "weight_bytes", "work")


def load_architecture(bench_dir: Path, config: dict) -> ModuleType:
    """The module `architectures/<model_type>.py` of `bench_dir`, loaded by
    its path: the plain reference (`make_weights`, `logits_for`), the bytes
    of the parameter tree (`weight_bytes`) and the bytes and FLOPs of each
    work a metric's file can name (`work`). There is no default and no table
    of names: a `model_type` without its file is a bad cell."""
    model_type = config.get("model_type")
    if not isinstance(model_type, str) or not model_type:
        raise ManifestError(f"configuration {config.get('name')!r} states no model_type")
    path = Path(bench_dir) / "architectures" / f"{model_type}.py"
    if not path.is_file():
        raise ManifestError(
            f"configuration {config.get('name')!r}: model_type {model_type!r} has no file {path}"
        )
    name = f"perfbench_architecture.{model_type}"
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses and pickling look a module up by its name
    spec.loader.exec_module(module)
    missing = [n for n in ARCH_GIVES if not callable(getattr(module, n, None))]
    if missing:
        raise ManifestError(f"{path} lacks {', '.join(missing)}")
    return module


def load_manifest(root: Path = ROOT) -> dict:
    return _read(Path(root) / "BENCHMARK.json")


def load_cell(
    workload: str,
    root: Path = ROOT,
    bench_dir: Path | None = None,
    rehearsal: bool = False,
) -> Cell:
    """The cell `workload` of `root`/BENCHMARK.json with its files.

    `rehearsal` lays each file's "rehearsal" group over it: the tiny
    sizes of a CPU run, which the command itself never uses.
    """
    root = Path(root)
    bench_dir = Path(bench_dir) if bench_dir else root / HERE.name
    manifest = load_manifest(root)
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == workload), None
    )
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise ManifestError(f"no workload {workload!r} (known: {known})")
    cfg_entry = next(
        (c for c in manifest["configs"] if c["name"] == entry["config"]), None
    )
    if cfg_entry is None:
        raise ManifestError(f"workload {workload!r}: no config {entry['config']!r}")
    config = _read(root / cfg_entry["file"])
    traffic = _read(bench_dir / "traffic" / f"{entry['traffic']}.json")
    if rehearsal:
        config = _merge(config, config.get("rehearsal", {}))
        traffic = _merge(traffic, traffic.get("rehearsal", {}))
    per_layer = []
    for m in manifest["per_layer"]:
        if not applies(m, workload):
            continue
        per_layer.append({**m, **_read(metric_file(bench_dir, m["name"]))})
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=config,
        traffic=traffic,
        limits=_read(bench_dir / "limits" / f"{entry['config']}.json"),
        arch=load_architecture(bench_dir, config),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m, workload)],
        per_layer=per_layer,
    )

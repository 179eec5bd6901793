"""Find a cell's files by name and build what they describe.

Everything a cell is made of is a file of its own under ``benchmarks/``:
``workloads/<cell>.json`` names ``configs/<config>.json`` and
``traffic/<mix>.json``; ``layer_metrics/<metric>.py`` are found by
listing the directory. Adding one needs no edit to a file that exists.
"""
from __future__ import annotations

import importlib
import json
import pathlib
from typing import Any, Dict, List

ROOT = pathlib.Path(__file__).resolve().parent
REPO = ROOT.parent


def _load(kind: str, name: str) -> dict:
    path = ROOT / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"benchmarks: no {kind[:-1]} file {path}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, tiny: bool = False) -> Dict[str, Any]:
    """The cell with its configuration and traffic mix read in. ``tiny``
    overlays the configuration's ``tiny`` sizes (CPU rehearsal only)."""
    cell = _load("workloads", name)
    cell["name"] = name
    config = _load("configs", cell["config"])
    if tiny:
        over = dict(config.get("tiny") or {})
        for group in ("serve", "train"):
            if group in over:
                config[group] = {**config.get(group, {}),
                                 **over.pop(group)}
        config.update(over)
        config.pop("head_dim", None)
    cell["config_name"] = cell["config"]
    cell["config"] = config
    cell["mix_name"] = cell["traffic"]
    cell["traffic"] = _load("traffic", cell["traffic"])
    return cell


def peaks() -> dict:
    with open(ROOT / "peaks.json") as f:
        return json.load(f)


def model_config(config: dict):
    """The program's config dataclass for a configuration file: the
    module ``skypilot_tpu.models.<family>``, its ``dataclass``, and the
    fields the file's ``fields`` map names (dataclass field -> published
    key)."""
    module = importlib.import_module(
        f"skypilot_tpu.models.{config['family']}")
    cls = getattr(module, config["dataclass"])
    kwargs = {field: config[key]
              for field, key in config["fields"].items()}
    return module, cls(**kwargs)


def layer_metrics(runner: str) -> List[Any]:
    """Every ``layer_metrics/<metric>.py`` that applies to ``runner``,
    loaded by path (a metric's name may hold dots)."""
    import importlib.util
    out = []
    for path in sorted((ROOT / "layer_metrics").glob("*.py")):
        if path.name.startswith("_"):
            continue
        spec = importlib.util.spec_from_file_location(
            "benchmarks.layer_metrics." + path.stem.replace(".", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if runner in mod.RUNNERS:
            out.append(mod)
    return out


def refusal(device: dict, cell: dict, tiny: bool) -> str:
    """Why this process may not run the cell here ('' = it may)."""
    if tiny:
        if device["platform"] == "tpu":
            return ("--tiny is a CPU rehearsal; it is refused on a "
                    "machine with a TPU")
        return ""
    if device["platform"] != "tpu":
        return (f"JAX found no TPU (platform {device['platform']!r}): "
                "a cell is measured on the chip or not at all")
    if device["kind"] not in peaks():
        return (f"device_kind {device['kind']!r} is not in "
                "benchmarks/peaks.json")
    if device["count"] < int(cell["chips"]):
        return (f"the cell needs {cell['chips']} chips, JAX sees "
                f"{device['count']}")
    return ""


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in jax.local_devices()]
    return int(max(peaks))

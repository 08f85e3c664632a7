"""Finding the benchmark's pieces by name.

Everything that belongs to one configuration, one traffic mix, one kind of
model, one driver or one per-layer metric is a file of its own under the
benchmark's directory; ``BENCHMARK.json`` names them.  A later PR adds
files and entries and edits nothing that is there.
"""
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_DIR = "chipbench"


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Catalog:
    """``BENCHMARK.json`` at ``root`` and the files under ``root/chipbench``."""

    def __init__(self, root=ROOT):
        self.root = root
        self.benchmark = _load_json(os.path.join(root, "BENCHMARK.json"))
        self._modules = {}

    def path(self, *parts):
        return os.path.join(self.root, DATA_DIR, *parts)

    def cell(self, name):
        """The cell's entry with its configuration's sizes (``sizes``) and
        its traffic mix's parameters (``mix``) loaded beside it."""
        cells = {w["name"]: w for w in self.benchmark["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                           f"{sorted(cells)}")
        cell = dict(cells[name])
        config = next(c for c in self.benchmark["configs"]
                      if c["name"] == cell["config"])
        cell["sizes"] = _load_json(os.path.join(self.root, config["file"]))
        cell["mix"] = _load_json(self.path("traffic",
                                           cell["traffic"] + ".json"))
        return cell

    def peak(self, device_kind):
        """The peaks of one chip of ``device_kind``; unknown is an error."""
        peaks = _load_json(self.path("peaks.json"))
        if device_kind.startswith("_") or device_kind not in peaks:
            raise KeyError(
                f"device kind {device_kind!r} is not in "
                f"{self.path('peaks.json')}; a peak is never guessed")
        return peaks[device_kind]

    def module(self, group, name):
        """``<root>/chipbench/<group>/<name>.py``, imported from its file."""
        key = (group, name)
        if key not in self._modules:
            path = self.path(group, name + ".py")
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{group}_{name.replace('-', '_')}", path)
            if spec is None or not os.path.exists(path):
                raise FileNotFoundError(f"no {group} named {name!r}: {path}")
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[key] = module
        return self._modules[key]

    def layer_metrics(self):
        """Every per-layer metric reader, by listing the directory."""
        names = sorted(f[:-3] for f in os.listdir(self.path("layer_metrics"))
                       if f.endswith(".py") and not f.startswith("_"))
        return [self.module("layer_metrics", n) for n in names]

    def metric_specs(self, section, cell_name):
        """The metrics of ``section`` that ``cell_name`` reports."""
        return [m for m in self.benchmark[section]
                if cell_name in m.get("workloads", [cell_name])]

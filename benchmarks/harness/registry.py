"""Finds a cell's parts by the names in BENCHMARK.json: a configuration, a
traffic mix, a data generator, a query and a metric reader are each one file
under benchmarks/, so a later PR adds a cell by adding files and manifest
entries and edits nothing that is here."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Registry:
    def __init__(self, bench_dir: str = BENCH_DIR, manifest: str = None):
        self.dir = bench_dir
        self.manifest_path = manifest or os.path.join(
            os.path.dirname(bench_dir), "BENCHMARK.json")
        with open(self.manifest_path) as fh:
            self.manifest = json.load(fh)

    def cell(self, name: str) -> dict:
        for cell in self.manifest["workloads"]:
            if cell["name"] == name:
                return cell
        raise SystemExit(f"no workload {name!r} in {self.manifest_path}")

    def data(self, kind: str, name: str) -> dict:
        """configs/<name>.json, traffic/<name>.json"""
        with open(os.path.join(self.dir, kind, name + ".json")) as fh:
            return json.load(fh)

    def module(self, kind: str, name: str):
        """datagen/<name>.py, queries/<name>.py, metrics/<name>.py — loaded
        by path, so a name may hold a dot (query_s.p50)."""
        path = os.path.join(self.dir, kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, cell_name: str, group: str) -> list:
        """The manifest's metrics of `group` (end_to_end | per_layer) that
        this cell reports."""
        return [m for m in self.manifest[group]
                if cell_name in m.get("workloads", [cell_name])]

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.dir, "peaks.json")) as fh:
            table = json.load(fh)["device_kind"]
        if device_kind not in table:
            raise SystemExit(
                f"no published peaks for device_kind {device_kind!r}: add "
                "it to benchmarks/peaks.json with its source")
        return table[device_kind]

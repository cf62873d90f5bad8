"""``BENCHMARK.json`` and the files it names: loading, lookup by name, and
validation of the manifest's form."""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The manifest at ``root`` and every file it names, resolved by name."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.manifest = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, kind: str, name: str) -> dict:
        for e in self.manifest[kind]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {kind} entry named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        """The configuration's file of sizes, as it is run."""
        return json.loads((self.root / self._entry("configs", name)["file"])
                          .read_text())

    def config_data(self, name: str):
        """The configuration's data generator, beside its file of sizes."""
        path = (self.root / self._entry("configs", name)["file"]).with_suffix(".py")
        return _load_module(path, f"bench_config_{name}")

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "bench" / "traffic" / f"{name}.json")
                          .read_text())

    @staticmethod
    def system(name: str):
        """The adapter of a system under test, ``bench/systems/<name>.py``."""
        return importlib.import_module(f"bench.systems.{name}")

    def limits(self, workload: str) -> dict:
        return json.loads((self.root / "bench" / "limits" / f"{workload}.json")
                          .read_text())

    def reader(self, metric: str):
        """The per-layer metric's reader: ``read(ctx) -> float | None``."""
        return _load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                            f"bench_metric_{metric}").read

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.root / "bench" / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           "bench/peaks.json")
        return table["devices"][device_kind]

    def end_to_end(self, workload: str) -> list[dict]:
        """The end-to-end metrics that ``workload`` reports."""
        return [m for m in self.manifest["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list[dict]:
        """The per-layer metrics that ``workload`` reports."""
        return [m for m in self.manifest["per_layer"]
                if workload in m.get("workloads", [workload])]


def validate(bench: Bench) -> list[str]:
    """Every way the manifest breaks its form; empty when it is sound."""
    m, errs = bench.manifest, []

    def need(ok: bool, msg: str) -> None:
        if not ok:
            errs.append(msg)

    need(set(m) == KEYS["top"], f"top-level keys {sorted(m)}")
    need(isinstance(m.get("command"), list) and 0 < len(m["command"]) <= 32,
         "command: a list of 1 to 32 strings")
    for word in m.get("command", []):
        need(isinstance(word, str) and 0 < len(word) <= 200
             and "\n" not in word and "\t" not in word, f"command word {word!r}")
        need(not word.startswith("/") and ".." not in word.split("/"),
             f"command word {word!r} leaves the repo")
    paths = m.get("paths", [])
    need(0 < len(paths) <= 16, "paths: 1 to 16 directories")
    for p in paths:
        need(bool(PATH.fullmatch(p)) and ".." not in p.split("/")
             and not p.startswith("/"), f"path {p!r}")
    need(isinstance(m.get("run_seconds"), int) and 1 <= m["run_seconds"] <= 51,
         "run_seconds: a whole number from 1 to 51")

    for kind, lo, hi in (("configs", 1, 24), ("workloads", 1, 24),
                         ("end_to_end", 1, 16), ("per_layer", 1, 128)):
        entries = m.get(kind, [])
        need(lo <= len(entries) <= hi, f"{kind}: {lo} to {hi} entries")
        names = [e.get("name", "") for e in entries]
        need(len(set(names)) == len(names), f"{kind}: names repeat")
        key = {"configs": "config", "workloads": "workload"}.get(kind, kind)
        for e in entries:
            extra = set(e) - KEYS[key] - ({"workloads"} if key in (
                "end_to_end", "per_layer") else set())
            need(not extra and KEYS[key] <= set(e),
                 f"{kind} {e.get('name')!r}: keys {sorted(e)}")
            need(bool(NAME.fullmatch(e.get("name", ""))),
                 f"{kind} name {e.get('name')!r}")
            for text in ("why", "layer", "source"):
                if text in e and kind in ("configs", "workloads", "per_layer"):
                    t = e[text]
                    need(isinstance(t, str) and 0 < len(t) <= 200
                         and "\n" not in t and "\t" not in t,
                         f"{kind} {e['name']!r}: {text} of 1 to 200 characters")

    configs = {c["name"]: c for c in m.get("configs", [])}
    cells = {w["name"]: w for w in m.get("workloads", [])}
    for c in configs.values():
        need(any(p and c["file"].startswith(p.rstrip("/") + "/")
                 for p in paths), f"config {c['name']!r}: file outside paths")
        need((bench.root / c["file"]).is_file(),
             f"config {c['name']!r}: no file {c['file']}")
        need(len(c["reduced"]) <= 16 and all(
            NAME.fullmatch(k) for k in c["reduced"]),
            f"config {c['name']!r}: reduced")
        need(any(w["config"] == c["name"] for w in cells.values()),
             f"config {c['name']!r} used by no cell")
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    need(len(set(pairs)) == len(pairs), "a (config, traffic) pair repeats")
    for w in cells.values():
        need(w["config"] in configs, f"cell {w['name']!r}: unknown config")
        need(bool(NAME.fullmatch(w["traffic"])),
             f"cell {w['name']!r}: traffic name")
        need(w["chips"] in (1, 4), f"cell {w['name']!r}: chips 1 or 4")
        need((bench.root / "bench" / "traffic" / f"{w['traffic']}.json")
             .is_file(), f"cell {w['name']!r}: no traffic file")
        need((bench.root / "bench" / "limits" / f"{w['name']}.json")
             .is_file(), f"cell {w['name']!r}: no limits file")
    need(sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2),
         "too many four-chip cells")

    e2e = {e["name"]: e for e in m.get("end_to_end", [])}
    need("setup_s" in e2e, "end_to_end: no setup_s")
    for e in list(e2e.values()) + m.get("per_layer", []):
        need(bool(UNIT.fullmatch(e.get("unit", ""))),
             f"metric {e['name']!r}: unit {e.get('unit')!r}")
        need(e.get("better") in ("lower", "higher"),
             f"metric {e['name']!r}: better")
        for w in e.get("workloads", []):
            need(w in cells, f"metric {e['name']!r}: unknown cell {w!r}")
    for e in e2e.values():
        need(e.get("source") in SOURCES_E2E, f"metric {e['name']!r}: source")
        b = e.get("bound")
        need(isinstance(b, (int, float)) and 0.01 <= b <= 0.25,
             f"metric {e['name']!r}: bound in [0.01, 0.25]")
    need(set(e2e).isdisjoint(p["name"] for p in m.get("per_layer", [])),
         "an end-to-end and a per-layer metric share a name")
    for p in m.get("per_layer", []):
        need(p.get("source") in SOURCES, f"metric {p['name']!r}: source")
        need(p.get("moves") in e2e, f"metric {p['name']!r}: moves")
        need((bench.root / "bench" / "metrics" / f"{p['name']}.py").is_file(),
             f"metric {p['name']!r}: no reader")
        moved = e2e.get(p.get("moves"), {})
        for w in p.get("workloads", list(cells)):
            need(w in moved.get("workloads", list(cells)),
                 f"metric {p['name']!r}: cell {w!r} does not report "
                 f"{p.get('moves')!r}")
    for w in cells:
        reported = [e["name"] for e in e2e.values()
                    if w in e.get("workloads", list(cells))]
        need("setup_s" in reported and len(reported) >= 2,
             f"cell {w!r}: needs setup_s and another end-to-end metric")
        need(any(w in p.get("workloads", list(cells))
                 for p in m.get("per_layer", [])),
             f"cell {w!r}: no per-layer metric")
    size = (bench.root / "BENCHMARK.json").stat().st_size
    need(size <= 64 * 1024, "BENCHMARK.json over 64 KiB")
    return errs

"""Packaging contract: pyproject console scripts resolve and the package
is installable metadata-wise."""

import importlib
import os
import re

try:
    import tomllib
except ImportError:  # Python 3.10: stdlib tomllib landed in 3.11
    tomllib = None

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mini_toml(path):
    """Fallback parser for exactly the pyproject shapes these tests read
    (table headers, string values, string arrays — including arrays that
    span lines), so the packaging contract stays tested on Python 3.10
    where tomllib is absent."""
    doc: dict = {}
    table = doc
    pending_key = None
    pending: list[str] | None = None
    with open(path, "r", encoding="utf-8") as f:
        for raw in f:
            line = raw.strip()
            if pending is not None:
                pending += re.findall(r'"((?:[^"\\]|\\.)*)"', line)
                if line.split("#")[0].rstrip().endswith("]"):
                    table[pending_key] = pending
                    pending = None
                continue
            if not line or line.startswith("#"):
                continue
            m = re.match(r"\[([^\]]+)\]$", line)
            if m:
                table = doc
                for part in m.group(1).split("."):
                    table = table.setdefault(part, {})
                continue
            m = re.match(r'(?:"([^"]+)"|([\w-]+))\s*=\s*(.*)$', line)
            if not m:
                continue
            key = m.group(1) or m.group(2)
            value = m.group(3).split("#")[0].strip() if not \
                m.group(3).startswith('"') else m.group(3)
            if value.startswith("["):
                strings = re.findall(r'"((?:[^"\\]|\\.)*)"', value)
                if value.rstrip().endswith("]"):
                    table[key] = strings
                else:
                    pending_key, pending = key, strings
            elif value.startswith('"'):
                table[key] = re.match(r'"((?:[^"\\]|\\.)*)"', value).group(1)
            elif value.startswith("{"):
                table[key] = dict(re.findall(
                    r'(\w+)\s*=\s*"((?:[^"\\]|\\.)*)"', value))
    return doc


def _pyproject():
    path = os.path.join(REPO, "pyproject.toml")
    if tomllib is not None:
        with open(path, "rb") as f:
            return tomllib.load(f)
    return _mini_toml(path)


def test_console_scripts_resolve():
    scripts = _pyproject()["project"]["scripts"]
    assert len(scripts) == 12  # ps/coordinator/worker + train/status/
    #                            generate/serve/eval/analyze/trace/ctl/
    #                            route
    for name, target in scripts.items():
        module, _, attr = target.partition(":")
        fn = getattr(importlib.import_module(module), attr)
        assert callable(fn), f"{name} -> {target} not callable"


def test_pinned_runtime_deps_importable():
    deps = _pyproject()["project"]["dependencies"]
    names = {d.split("==")[0].split(">=")[0].strip() for d in deps}
    assert {"jax", "optax", "grpcio", "numpy", "ml_dtypes"} <= names
    for mod in ("jax", "optax", "grpc", "numpy", "ml_dtypes"):
        importlib.import_module(mod)


def test_native_source_shipped_as_package_data():
    data = _pyproject()["tool"]["setuptools"]["package-data"]
    assert "*.cpp" in data["parameter_server_distributed_tpu.native"]
    assert os.path.exists(os.path.join(
        REPO, "parameter_server_distributed_tpu", "native",
        "psdt_native.cpp"))


def test_analysis_goldens_shipped_as_package_data():
    # pst-analyze needs the golden wire manifest, the per-extension
    # protocol manifests, the knob registry, and the reviewed baseline
    # from an installed copy, not just a checkout
    data = _pyproject()["tool"]["setuptools"]["package-data"]
    assert "*.json" in data["parameter_server_distributed_tpu.analysis"]
    for fname in ("wire_manifest.json", "ext_manifests.json",
                  "knob_registry.json", "baseline.json"):
        assert os.path.exists(os.path.join(
            REPO, "parameter_server_distributed_tpu", "analysis", fname))

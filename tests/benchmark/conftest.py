"""Fixtures of the benchmark's own tests: its modules, loaded by path
(`benchmark/` is a directory of scripts, not a package)."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def load_script(rel: str):
    name = "bench_" + rel.replace("/", "_").replace(".py", "").replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def bench_run():
    return load_script("run.py")


@pytest.fixture(scope="session")
def serial():
    return load_script("references/ycsb_serial.py")


@pytest.fixture(scope="session")
def loadgen():
    return load_script("loadgen.py")


@pytest.fixture(scope="session")
def trace_reduce():
    return load_script("trace_reduce.py")

"""The harness's own tests run on virtual CPU devices, at a tiny size,
against ``data/root`` — a checkout root of its own (a BENCHMARK.json, a
configuration, a mix, a cell and two layer metrics, all files the
unchanged harness finds by name)."""

import os
import pathlib
import sys
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the harness turns the persistent compile cache on; keep the tests'
# programs out of the checkout's own cache
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "benchmark-tests-jax-cache"))

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

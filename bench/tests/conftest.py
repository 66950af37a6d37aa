"""Test set-up for the benchmark's own tests.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They run on the CPU at the small ``mphx-2p-8x8`` plane and import the
harness's modules the way ``bench/run.py`` does.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (BENCH, os.path.join(ROOT, "src")):
    if path not in sys.path:
        sys.path.insert(0, path)


@pytest.fixture
def x64():
    """64-bit mode on, as a TPU has it for the solver: the program's
    ``auto`` backend then resolves to ``jax`` on the CPU too."""
    import jax

    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", old)


@pytest.fixture(scope="session")
def small_config():
    import run

    return run.load_json(os.path.join(HERE, "mphx-2p-8x8.json"))


@pytest.fixture(scope="session")
def spec():
    import run

    return run.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.fixture(scope="session")
def dal_config():
    """``mphx-2p-8x8`` routed with DAL (``valiant``)."""
    import run

    return run.load_json(os.path.join(HERE, "mphx-2p-8x8-dal.json"))

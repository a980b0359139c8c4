"""Fixtures of the benchmark's CPU tests."""
from pathlib import Path

import pytest

from bench.tests.tiny import write_tiny_root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return write_tiny_root(tmp_path_factory.mktemp("tiny_bench"))

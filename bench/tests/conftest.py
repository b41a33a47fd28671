"""Fixtures of the benchmark's CPU tests."""

import pytest

from bench.tests.cells import make_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)

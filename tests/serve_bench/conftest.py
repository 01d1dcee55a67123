"""Fixtures of the serving benchmark's own tests."""

import pytest
from servebench_fixtures import make_root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path / "bench")

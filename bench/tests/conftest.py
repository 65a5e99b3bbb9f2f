import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import perfbench_paths  # noqa: E402,F401
from perfbench_fixtures import make_tiny_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)

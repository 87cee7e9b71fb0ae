import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (HERE, os.path.dirname(os.path.dirname(HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="session")
def benchmark_json():
    from pb_helpers import bench

    return bench()

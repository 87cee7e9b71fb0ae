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


@pytest.fixture(scope="session")
def xl_rehearsal(tmp_path_factory):
    """One harness process of the XL cell on the CPU at tiny widths, traced, its capture and numbers kept: shared by
    every test file of the session that needs one (one process where the files run in one, as in the rehearsed tree
    of `test_pb_addition.py`); (rc, stdout lines, stderr, the directory kept). A window of 5 s, not 2: on a machine
    that six workers and their harness processes starve, a train call has taken 2.25 s (it takes 0.08 s), and the
    window has to hold three for the spans' counts to have something to add up."""
    from pb_helpers import XL_CELL, run_harness

    keep = str(tmp_path_factory.mktemp("keep"))
    rc, out, err = run_harness("--workload", XL_CELL, "--seed", "3000000021", "--seconds", "5", "--trace", "1",
                               "--rehearse-cpu", "--keep", keep, "--keep-trace", "1")
    return rc, out, err, keep

"""Operation and byte counts of the kernels' work at known shapes."""
from chipbench.lib.registry import Bench


def test_group_occupancy_bytes():
    w = Bench().work("group_occupancy").cost({"cells": 65536, "lanes": 1})
    assert w == {"flops": 65536, "bytes": 12 * 65536}


def test_queue_admit_bytes():
    w = Bench().work("queue_admit").cost({"cells": 65536, "lanes": 39322})
    assert w == {"flops": 0, "bytes": 13 * 39322 + 12 * 65536}

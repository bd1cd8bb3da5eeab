"""The control, the reference computed one precision below the
configuration's and put in the program's place, fails the real limits;
the reference checked against itself passes them."""
import pytest

from chipbench.lib import check
from chipbench.lib.registry import Bench
from chipbench.tests.small import CUT


@pytest.mark.parametrize("wl", list(CUT))
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 2**33 + 1])
def test_control_fails_reference_passes(small_root, wl, seed):
    bench = Bench(small_root)
    w = bench.workload(wl)
    cfg, mix = bench.config(w["config"]), bench.traffic(w["traffic"])
    limits = bench.limits(wl)
    entry = bench.entry(cfg["entry"])
    cell = entry.build(cfg, mix, seed)
    ctl = check.control_report(cell.setup, *check.control_dtypes(cfg))
    assert not check.judge(check.compare_serve(cell.setup, ctl),
                           limits)["correct"]
    own = check.control_report(cell.setup, *check.reference_dtypes())
    assert check.judge(check.compare_serve(cell.setup, own),
                       limits)["correct"]


def test_unrecorded_rounds_compare_by_sum(small_root):
    """Decisions of rounds the program never finished have no record, so
    the reference makes them itself: in a window whose snapshot holds
    them, the device/edge/cloud slot counts are compared by their sum;
    in every other window each count is compared."""
    import copy

    import numpy as np

    from chipbench.reference import serve as ref
    bench = Bench(small_root)
    w = bench.workload("small_live")
    cfg = bench.config(w["config"])
    mix = dict(bench.traffic(w["traffic"]), horizon_ms=4000.0)
    limit = bench.limits("small_live")["telemetry_err"]
    entry = bench.entry(cfg["entry"])
    cell = entry.build(cfg, mix, 2**31 + 11)
    rep = entry.one_pass(cell).report
    teacher = {k: np.asarray(v) for k, v in rep["records"].items()}
    loose = ref.simulate(cell.setup, teacher=teacher)["telemetry"]["unforced"]
    assert loose[-1] and not loose[0]
    assert check.compare_serve(cell.setup, rep)["telemetry_err"] == 0.0

    def moved(window, edge=1, local=-1):
        r = copy.deepcopy(rep)
        s = r["telemetry"]["series"]
        s["occ_edge"][window] += edge
        s["occ_local"][window] += local
        return check.compare_serve(cell.setup, r)["telemetry_err"]
    assert moved(len(loose) - 1) == 0.0
    assert moved(len(loose) - 1, local=0) > limit
    assert moved(0) > limit

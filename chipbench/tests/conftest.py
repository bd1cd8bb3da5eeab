import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "src"))


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    from chipbench.tests.small import make_root
    return make_root(tmp_path_factory.mktemp("small"))

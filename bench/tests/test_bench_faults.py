"""A run with the timed path broken underneath must come out not correct.

The harness's look for a chip is skipped and the rest of a run is
driven at a CPU size, with the service replaced by one that breaks its
answers where they are produced: one answer altered, or half of the
batch's answers left out.  (The cells run on one chip, so there is no
exchange between chips to leave out, and no step state to freeze.)"""

import pytest

import tinybench
from repro.core.discovery import DiscoveryService


def _alter(res):
    """Raise the MI of the answer's first entry."""
    if res:
        meta, mi, js = res[0]
        res = [(meta, mi + 0.5, js)] + list(res[1:])
    return res


class Broken(DiscoveryService):
    fault = None

    def submit(self, queries, **kw):
        res = super().submit(queries, **kw)
        if self.fault == "altered":
            return [_alter(res[0])] + res[1:]
        return [r if i % 2 == 0 else [] for i, r in enumerate(res)]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinybench.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("name", ["lake256-screen", "lake1024-screen"])
@pytest.mark.parametrize("fault", ["altered", "dropped"])
def test_broken_answers_are_not_correct(root, name, fault):
    def factory(**kw):
        svc = Broken(**kw)
        svc.fault = fault
        return svc

    out = tinybench.run_cell(root, name, service_factory=factory)
    assert not out["correct"], out["checks"]
    bad = {k for k, c in out["checks"].items() if c["value"] > c["limit"]}
    if fault == "altered":
        assert "mi_gap" in bad
    else:
        assert "wrong" in bad

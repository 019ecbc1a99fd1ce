"""A mix's ``complete_columns``: the complete checks cover exactly that
many columns drawn from the seed, and a list that misses a passing
column of the sample is wrong."""

import json

import pytest

import tinybench
from harness import cell, compare, traffic
from repro.core.discovery import DiscoveryService

SEED = 2**31 + 17
SAMPLE = 200

# A lake module whose reference leaves beside it, one JSON line a call,
# the columns each answer was asked to estimate (null for all).
RECORDING_LAKE = """
import json
import pathlib

from harness import reference
from harness.lake import Lake
from harness.reference import (LakeSketches, check_options, join, kind_of,
                               ranked)
from harness.traffic import requests

ASKED = pathlib.Path(__file__).with_suffix(".asked")


def answer(lake, ls, q, *, only=None, **kw):
    with ASKED.open("a") as f:
        f.write(json.dumps(None if only is None
                           else sorted(int(c) for c in only)) + "\\n")
    return reference.answer(lake, ls, q, only=only, **kw)
"""


@pytest.fixture
def root(tmp_path):
    root = tinybench.make_root(tmp_path)
    b = root / "bench"
    (b / "lakes" / "recording.py").write_text(RECORDING_LAKE)
    cfg = json.loads((b / "configs" / "tiny.json").read_text())
    cfg["lake"] = "recording"
    (b / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "screen.json").read_text())
    mix["complete_columns"] = SAMPLE
    (b / "traffic" / "screen.json").write_text(json.dumps(mix))
    return root


def test_complete_checks_cover_the_seeded_sample(root):
    out = tinybench.run_cell(root, "lake256-screen", seed=SEED)
    assert out["correct"], out["checks"]
    asked = [json.loads(line) for line in (
        root / "bench" / "lakes" / "recording.asked").read_text().splitlines()]
    assert None not in asked
    mix = json.loads((root / "bench" / "traffic" / "screen.json").read_text())
    top_k = mix["top_k"]
    sample = set(cell.complete_sample(SEED, tinybench.TINY["tables"],
                                      SAMPLE).tolist())
    assert len(sample) == SAMPLE
    wide = [set(a) for a in asked if len(a) > top_k]
    # The sample and the served columns, in each answer of the requests
    # checked in full; the served columns alone in every other one.
    assert len(wide) == mix["complete_checks"] * len(mix["targets"])
    for a in wide:
        assert sample <= a and len(a - sample) <= top_k
    assert len(asked) > len(wide)


class DropsASampleColumn(DiscoveryService):
    """Leaves the first column of the sample out of each list and moves
    the next one up, so the list stays ``top_k`` long and in order."""

    def submit(self, queries, *, top_k, **kw):
        sample = set(cell.complete_sample(SEED, tinybench.TINY["tables"],
                                          SAMPLE).tolist())
        out = []
        for r in super().submit(queries, top_k=top_k + 1, **kw):
            cols = [compare.column_of(t) for t, _, _ in r]
            drop = next((i for i, c in enumerate(cols) if c in sample),
                        len(r) - 1)
            out.append((r[:drop] + r[drop + 1:])[:top_k])
        return out


def test_a_list_missing_a_sample_column_is_wrong(root):
    out = tinybench.run_cell(root, "lake256-screen", seed=SEED,
                             service_factory=DropsASampleColumn)
    assert not out["correct"], out["checks"]
    assert out["checks"]["wrong"]["value"] > 0
    assert out["checks"]["mi_gap"]["value"] <= compare.LIMITS["mi_gap"]


@pytest.mark.parametrize("value", [0, -3])
def test_a_mix_refuses_complete_columns_under_one(tmp_path, value):
    path = tmp_path / "mix.json"
    mix = json.loads((tinybench.BENCH / "traffic" / "screen.json")
                     .read_text())
    path.write_text(json.dumps(dict(mix, complete_columns=value)))
    with pytest.raises(ValueError, match="complete_columns"):
        traffic.load(str(path))

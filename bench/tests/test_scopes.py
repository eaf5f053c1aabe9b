"""bench/scopes.py on a small trace recorded on one v5e chip
(bench/record_scope_fixture.py): two named scopes, one of them a
``lax.scan``, under host spans, reduced to the numbers written beside
it, which were counted without interval unions."""
import json
from pathlib import Path

import pytest

from bench import scopes as S
from bench import trace_reduce as T

FIX = Path(__file__).resolve().parents[1] / "fixtures"
TRACE = FIX / "scopes_3steps.xplane.pb"
OLD_TRACE = FIX / "matmul_sort_3steps.xplane.pb"


@pytest.fixture(scope="module")
def hlo():
    return (FIX / "scopes_3steps.hlo.txt").read_text()


@pytest.fixture(scope="module")
def reduced(hlo):
    return S.reduce(TRACE, hlo, scopes=("mix", "loop"))


@pytest.fixture(scope="module")
def expected():
    return json.loads((FIX / "scopes_3steps.json").read_text())


def test_window_agrees_with_trace_reduce(reduced):
    red = T.reduce(TRACE)
    for key in ("window_s", "busy_s", "steps"):
        assert reduced[key] == pytest.approx(red[key], rel=1e-12), key
    assert reduced["steps"] == 3


def test_scope_unions(reduced, expected):
    sc = reduced["scopes"]
    assert sc["mix"] == pytest.approx(expected["mix"], rel=1e-9)
    # the loop's while op holds its body's ops: counted once, as the
    # while's own duration, not as the sum over every op of the scope
    assert sc["loop"] == pytest.approx(expected["loop"], rel=1e-9)
    assert expected["loop_whiles"] == 3
    assert sc["loop"] < expected["loop_all_ops"]
    assert sc["mix"] > 0 and sc["loop"] > sc["mix"]
    # every op of the window is the step's, and the scopes and the
    # unscoped sum cover busy time once
    assert sc[S.NOT_IN_HLO] == pytest.approx(0.0, abs=1e-12)
    assert sc["mix"] + sc["loop"] + sc[S.UNSCOPED] == pytest.approx(
        reduced["busy_s"], rel=1e-9)
    assert sc[S.UNSCOPED] > 0
    # the unscoped sum with its op_name; an unscoped op that runs inside
    # the loop's while (an inserted copy) adds no unscoped time
    tops = {k: (v, o) for k, v, o in reduced["top_unscoped"]}
    assert tops["convert_reduce_fusion"][1] == "jit(step)/reduce_sum"
    assert sc[S.UNSCOPED] < sum(v for v, _ in tops.values())


def test_host_spans(reduced, expected):
    for span in ("pipeline.batch", "pipeline.markov"):
        secs, count = reduced["host"][span]
        assert count == expected[span][1] == 3
        assert secs == pytest.approx(expected[span][0], rel=1e-9)
    assert 0.019 * 3 < reduced["host"]["pipeline.batch"][0] < 0.03 * 3
    assert reduced["host"]["pipeline.markov"][0] < \
        reduced["host"]["pipeline.batch"][0]


def test_unknown_ops_are_not_in_the_hlo():
    red = S.reduce(OLD_TRACE, "")
    assert red["scopes"][S.NOT_IN_HLO] == pytest.approx(red["busy_s"],
                                                        rel=1e-12)
    assert all(v == 0.0 for k, v in red["scopes"].items()
               if k != S.NOT_IN_HLO)
    assert red["top_not_in_hlo"][0][0] == "sort.6"
    assert red["host"]["host.sleep"][1] == 3


def test_table_is_per_step(reduced):
    rows = S.table(reduced).splitlines()
    assert rows[0].split() == ["scope", "ms/step", "%", "busy"]
    loop = next(r for r in rows if r.startswith("loop"))
    assert float(loop.split()[1]) == pytest.approx(
        1e3 * reduced["scopes"]["loop"] / 3, abs=1e-3)

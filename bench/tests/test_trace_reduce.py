"""bench/trace_reduce.py on a small trace recorded on one v5e chip
(bench/record_fixture.py), reduced to the numbers written beside it."""
import json
from pathlib import Path

import pytest

from bench import manifest as M
from bench import trace_reduce as T

FIX = Path(__file__).resolve().parents[1] / "fixtures"
TRACE = FIX / "matmul_sort_3steps.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(TRACE)


@pytest.fixture(scope="module")
def expected():
    return json.loads((FIX / "matmul_sort_3steps.json").read_text())


def test_window_busy_and_ops(reduced, expected):
    assert reduced["devices"] == expected["devices"] == 1
    assert reduced["steps"] == expected["steps"] == 3
    assert reduced["window_s"] == pytest.approx(expected["window_s"],
                                                rel=1e-12)
    assert reduced["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-12)
    top = [[k, v] for k, v in list(reduced["ops"].items())[:3]]
    assert [k for k, _ in top] == [k for k, _ in expected["top_ops"]]
    assert [v for _, v in top] == pytest.approx(
        [v for _, v in expected["top_ops"]], rel=1e-12)
    # by hand: three 20 ms sleeps idle the chip, the sort keeps it busy
    assert reduced["ops"]["sort.6"] / 3 == pytest.approx(7.3e-3, rel=0.05)
    assert 0.06 < reduced["window_s"] - reduced["busy_s"] < 0.07


def test_gaps_are_named_by_the_host(reduced, expected):
    gaps = reduced["idle_gaps"]
    assert [g[0] for g in gaps[:3]] == ["host.sleep"] * 3
    for (name, secs), (want_name, want) in zip(gaps, expected["idle_gaps"]):
        assert name == want_name and secs == pytest.approx(want, rel=1e-12)
        assert secs <= reduced["window_s"]
    assert all(0.019 < s < 0.023 for _, s in gaps[:3])


def test_readers_on_the_fixture(reduced):
    reading = {"red": reduced, "steps": reduced["steps"], "chips": 1,
               "peak": {"bf16_flops_per_s": 197e12},
               "flops_per_step": 2 * 2048 ** 3}
    idle = M.load_module(M.metric_path("device_idle_pct")).read(reading)
    assert idle == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))
    sort_ms = M.load_module(M.metric_path("topk_sort_ms")).read(reading)
    assert sort_ms == pytest.approx(1e3 * reduced["ops"]["sort.6"] / 3)
    mfu = M.load_module(M.metric_path("mfu")).read(reading)
    assert 0 < mfu < 100
    no_sort = dict(reading, red=dict(reduced, ops={"fusion.1": 1.0}))
    assert M.load_module(M.metric_path("topk_sort_ms")).read(
        no_sort) is None


def test_op_names_and_breakdown(reduced):
    assert T.op_name("%sort.5 = (f32[1,4]) sort(f32[1,4] %a)") == "sort.5"
    b = T.breakdown(reduced)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(v, float) for _, v in b["device_ops"])

"""The reduction from a trace to busy, idle and per-operation time."""
import json
import pathlib

import pytest

from bench.trace import reduce

DATA = pathlib.Path(__file__).parent / "data"


def test_scripted_trace():
    ms = 1_000_000
    trace = {
        "device": {"/device:TPU:0": [
            ["fusion.1", 0 * ms, 2 * ms],      # starts before the window
            ["while.1", 3 * ms, 6 * ms],
            ["kernel", 4 * ms, 5 * ms],        # nested: union counts once
            ["fusion.1", 9 * ms, 12 * ms],     # ends after the window
        ]},
        "host": [["bench.window", 1 * ms, 10 * ms],
                 ["bench.job", 1 * ms, 10 * ms],
                 ["bench.decode_step", 2 * ms, 3 * ms],
                 ["bench.sample", 6 * ms, 9 * ms],
                 ["bench.page_table", 7 * ms, 8 * ms]],
    }
    r = reduce(trace)
    assert r["window_s"] == pytest.approx(9e-3)
    # busy: [1,2] + [3,6] + [9,10] = 5 ms
    assert r["busy_s"] == pytest.approx(5e-3)
    assert r["ops"]["while.1"] == pytest.approx(2e-3)
    assert r["ops"]["kernel"] == pytest.approx(1e-3)
    assert r["ops"]["fusion.1"] == pytest.approx(2e-3)
    idle = dict(r["idle_gaps"])
    # gap [2,3] mid 2.5: decode_step; gap [6,9] mid 7.5: the inner
    # page_table span wins over sample
    assert idle == pytest.approx({"bench.decode_step": 1e-3,
                                  "bench.page_table": 3e-3})


def test_two_chips_average():
    trace = {"device": {"/device:TPU:0": [["a", 0, 10]],
                        "/device:TPU:1": [["a", 0, 5]]},
             "host": [["bench.window", 0, 10]]}
    r = reduce(trace)
    assert r["busy_s"] == pytest.approx(7.5e-9)
    assert dict(r["idle_gaps"]) == pytest.approx({"host: serve loop": 2.5e-9})


def test_no_device_work_is_an_error():
    with pytest.raises(RuntimeError):
        reduce({"device": {"/device:TPU:0": []},
                "host": [["bench.window", 0, 10]]})


def test_recorded_v5e_trace():
    """100 ms from the middle of a traced qwen1.5-0.5b.chat window on a
    TPU v5e (decode steps), as read_xplane lists it."""
    trace = json.loads((DATA / "trace_v5e_qwen_chat.json").read_text())
    r = reduce(trace)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.096439201)
    # own times never exceed the busy time they nest in
    assert sum(r["ops"].values()) <= r["busy_s"] * (1 + 1e-9)
    top = dict(r["device_ops"])
    assert r["device_ops"][0][0] == "paged_decode_attention.8 = bf16[32,16,1,64]"
    assert top["paged_decode_attention.8 = bf16[32,16,1,64]"] == \
        pytest.approx(0.0236757945)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_nested_operations_count_their_own_time():
    trace = {"device": {"/device:TPU:0": [["while.4", 0, 10],
                                          ["kernel", 2, 5],
                                          ["copy", 6, 7]]},
             "host": [["bench.window", 0, 10]]}
    r = reduce(trace)
    assert r["ops"] == pytest.approx({"while.4": 6e-9, "kernel": 3e-9,
                                      "copy": 1e-9})
    assert r["busy_s"] == pytest.approx(10e-9)

"""The reduction from trace events to busy and idle time, time by category
and labelled gaps: on a case small enough to check by hand, and on the
events recorded from a chip run (bench/fixtures/)."""
import glob
import json
import os

import pytest

from bench import trace_reduce as tr
from bench.tests.conftest import ROOT

FIXTURES = os.path.join(ROOT, "bench", "fixtures")

# one chip: a while loop 1.0-3.0 holding a fusion 1.2-1.7 and a sort
# 1.8-2.8, then a fusion 4.0-4.5; the query's span 0.5-4.7, its fetch
# 4.2-4.7
WHILE, FUSION2, SORT, FUSION9 = (
    "jit_a/%while.1 while tuple", "jit_a/%fusion.2 fusion:kLoop f32[8]",
    "jit_a/%sort.3 sort u32[8]", "jit_b/%fusion.9 fusion:kCustom s32[8]")
BY_HAND = {
    "devices": {"/device:TPU:0": [[WHILE, 1.0, 2.0], [FUSION2, 1.2, 0.5],
                                  [SORT, 1.8, 1.0], [FUSION9, 4.0, 0.5]]},
    "host": [["bench.query", 0.5, 4.2], ["bench.fetch", 4.2, 0.5]],
}


def test_by_hand():
    r = tr.reduce(BY_HAND)
    assert r["window_s"] == pytest.approx(4.2)
    assert r["queries"] == 1 and r["chips"] == 1
    assert r["busy_s"] == pytest.approx(2.5)  # the union, not the sum 4.0
    # each instant goes to the innermost operation
    assert r["ops_s"] == pytest.approx({WHILE: 0.5, FUSION2: 0.5,
                                        SORT: 1.0, FUSION9: 0.5})
    assert r["categories_s"] == pytest.approx({"other": 1.5, "sort": 1.0})
    assert r["modules_s"] == pytest.approx({"jit_a": 2.0, "jit_b": 0.5})
    # gaps: 0.5-1.0 and 3.0-4.0 in the query's body, 4.5-4.7 in its fetch
    assert r["idle_by_label_s"] == pytest.approx(
        {"query": 1.5, "fetch": 0.2, "between": 0.0})
    assert [g[0] for g in r["gaps"]] == ["query", "query", "fetch"]
    assert [g[1] for g in r["gaps"]] == pytest.approx([1.0, 0.5, 0.2])
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["jit_a/*", pytest.approx(2.0)]
    assert b["device_ops"][2] == [SORT, pytest.approx(1.0)]
    assert len(b["device_ops"]) == 6
    assert len(b["idle_gaps"]) <= 10


def test_idle_between_queries_and_the_idlest_chip():
    events = {
        "devices": {"/device:TPU:0": [[FUSION2, 0.0, 1.0],
                                      [FUSION2, 2.0, 1.0]],
                    "/device:TPU:1": [["jit_s/%all-to-all.4 all-to-all u32[8]",
                                       0.0, 0.5],
                                      [FUSION2, 2.0, 1.0]]},
        "host": [["bench.query", 0.0, 1.0], ["bench.fetch", 0.9, 0.1],
                 ["bench.query", 2.0, 1.0], ["bench.fetch", 2.9, 0.1]],
    }
    r = tr.reduce(events)
    assert r["window_s"] == pytest.approx(3.0) and r["queries"] == 2
    assert r["busy_s"] == pytest.approx(1.75)      # mean over the chips
    assert r["busy_s_min"] == pytest.approx(1.5)   # chip 1 is the idlest
    assert r["categories_s"]["collective"] == pytest.approx(0.25)
    assert r["idle_by_label_s"] == pytest.approx(
        {"query": 0.4, "fetch": 0.1, "between": 1.0})


def test_nothing_to_read():
    assert tr.reduce({"devices": {}, "host": []}) == {}
    assert tr.reduce({"devices": {"/device:TPU:0": [["a", 0, 1]]},
                      "host": []}) == {}


# names as the profiler of a TPU v5e gave them (my chip runs, PR 24; the
# last is of the same form, not recorded)
HLO = {
    "sort": ("%sort = (u32[33554432]{0:T(1024)}, u32[33554432]{0:T(1024)}) "
             "sort(u32[33554432]{0:T(1024)} %get-tuple-element.25, "
             "u32[33554432]{0:T(1024)} %get-tuple-element.24), "
             "dimensions={0}, to_apply=%region_0.2"),
    "gather": ("%fusion.15 = s32[16777216]{0:T(1024)} fusion(s32[16777216]"
               "{0:T(1024)} %get-tuple-element.17, s32[16777216]{0:T(1024)} "
               "%fusion.38), kind=kCustom, calls=%fused_computation.2.clone"),
    "bitcast": ("%custom-call.26 = pred[16777216]{0:T(1024)(128)(4,1)S(1)} "
                "custom-call(pred[4194304]{0:T(1024)(128)(4,1)S(1)} "
                "%slice-done), custom_call_target=\"ConcatBitcast\""),
    "pallas": ("%_scan_padded.3 = (s32[128,262144]{1,0:T(8,128)}, s32[128,1]"
               "{1,0:T(8,128)S(1)}) custom-call(s32[128,262144]{1,0:T(8,128)}"
               " %reshape.6), custom_call_target=\"tpu_custom_call\", "
               "operand_layout_constraints={s32[128,262144]{1,0}}"),
    "exchange": ("%ragged_all_to_all.20 = u32[4194304,1,128]{2,1,0:T(1,128)} "
                 "ragged-all-to-all(u32[2097152,1,128]{2,1,0:T(1,128)} "
                 "%copy.12, u32[4194304,1,128]{2,1,0:T(1,128)} "
                 "%ragged_all_to_all.19, s32[4]{0:T(128)} %pad_add_fusion.1), "
                 "channel_id=1, replica_groups={{0,1,2,3}}"),
    "exchange-pad": ("%ragged_all_to_all.18 = u32[128]{0:T(128)} pad(u32[2]"
                     "{0:T(128)S(1)} %broadcast_in_dim.41, u32[]{:T(128)} "
                     "%constant.84), padding=0_126"),
    "all-reduce": ("%all-reduce.2 = s32[16]{0:T(128)S(1)} all-reduce(s32[16]"
                   "{0:T(128)S(1)} %dynamic-update-slice), channel_id=2, "
                   "replica_groups={{0,1,2,3}}, to_apply=%add"),
    "gather-start": ("%all-gather-start = (s32[4]{0}, s32[16]{0}) "
                     "all-gather-start(s32[4]{0:T(128)} %sort.2), "
                     "dimensions={0}"),
}


@pytest.mark.parametrize("key,short,cat", [
    ("sort", "jit_m/%sort sort tuple", "sort"),
    ("gather", "jit_m/%fusion.15 fusion:kCustom s32[16777216]", "other"),
    ("bitcast", "jit_m/%custom-call.26 custom-call:ConcatBitcast "
                "pred[16777216]", "other"),
    ("pallas", "jit_m/%_scan_padded.3 custom-call:tpu_custom_call tuple",
     "pallas"),
    ("exchange", "jit_m/%ragged_all_to_all.20 ragged-all-to-all "
                 "u32[4194304,1,128]", "collective"),
    ("exchange-pad", "jit_m/%ragged_all_to_all.18 pad u32[128]", "other"),
    ("all-reduce", "jit_m/%all-reduce.2 all-reduce s32[16]", "collective"),
    ("gather-start", "jit_m/%all-gather-start all-gather-start tuple",
     "collective")])
def test_short_names_and_categories(key, short, cat):
    """An operand that is called %sort does not make an operation a sort,
    nor the name %ragged_all_to_all a pad an exchange: the category is the
    operation's own opcode."""
    assert tr.short_name(HLO[key], "jit_m") == short
    assert tr.category(short) == cat


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    FIXTURES, "*.events.json"))), ids=os.path.basename)
def test_recorded_fixture(path):
    """Events recorded on the chip reduce to the numbers recorded beside
    them, and those hold together: categories add up to busy time, gaps to
    idle time."""
    with open(path) as f:
        events = json.load(f)
    with open(path.replace(".events.json", ".expected.json")) as f:
        expected = json.load(f)
    r = tr.reduce(events)
    for key in ("window_s", "queries", "chips", "busy_s", "busy_s_min",
                "query_s"):
        assert r[key] == pytest.approx(expected[key], rel=1e-9), key
    assert r["categories_s"] == pytest.approx(expected["categories_s"])
    assert r["idle_by_label_s"] == pytest.approx(expected["idle_by_label_s"])
    assert sum(r["categories_s"].values()) == pytest.approx(r["busy_s"])
    assert sum(r["idle_by_label_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s_min"])
    assert 0 < r["busy_s_min"] <= r["busy_s"] <= r["window_s"]

"""The trace reduction, on a trace recorded on one v5e chip: two traced
single-column cgnr solves at 16^4 inside ``bench.window`` and
``bench.solve`` spans (``data/small.xplane.pb.gz``)."""
import gzip
import pathlib

import pytest

from chipbench import trace
from chipbench.bench import Bench

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb.gz"
HALF_SITES = 16 * 16 * 16 * 8


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(gzip.decompress(SMALL.read_bytes()))
    return trace.reduce(str(path))


def test_busy_and_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    # Ops of the ops line do not overlap once the loops that contain
    # them are left out, so their times add up to no more than busy.
    assert sum(v["s"] for v in reduced["ops"].values()) <= (
        reduced["busy_s"] * (1 + 1e-9))


def test_kernels_by_name(reduced):
    calls = {c["name"]: c for c in reduced["kernels"]}
    assert set(calls) == {"wilson_hop_eo", "wilson_hop_oe"}
    for c in calls.values():
        assert c["dtype"] == "f32" and c["elems"] == HALF_SITES * 24
        assert c["calls"] > 30 and c["s"] > 0
    top = [name for name, _ in reduced["device_ops"]]
    assert top[0] in calls and len(top) <= trace.TOP


def test_gaps_are_labelled_by_the_host(reduced):
    gaps = reduced["idle_gaps"]
    assert 0 < len(gaps) <= trace.TOP
    assert all(s > 0 for _, s in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert any(label.startswith("bench.solve") for label, _ in gaps)


def test_roofline_from_the_trace(reduced):
    rec = {"trace": reduced, "cell": {"config": {"extents": [16] * 4}},
           "device_kind": "TPU v5 lite", "root": Bench().dir}
    share = Bench().reader("dhat_roofline")(rec)
    assert 0 < share < 100


def test_union_clip_and_parse():
    assert trace.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
    op = trace.parse_op(
        "%wilson_hop_eo.13 = f32[12,16,16,24,16,8]{5,4,3,2,1,0:T(8,128)} "
        "custom-call(s32[1]{0:T(128)} %constant.4), "
        'custom_call_target="tpu_custom_call"')
    assert op == {"name": "wilson_hop_eo", "opcode": "custom-call",
                  "dtype": "f32", "elems": 12 * 16 * 16 * 24 * 16 * 8,
                  "kernel": True}
    op = trace.parse_op('%custom-call.1 = f32[16,8]{1,0} custom-call(%a), '
                        'custom_call_target="ConcatBitcast"')
    assert op["opcode"] == "custom-call" and not op["kernel"]
    op = trace.parse_op("%while = (f32[4]{0}, s32[]) while(%t), body=%b")
    assert op["opcode"] == "while" and op["elems"] is None
    assert trace.parse_op("%broadcast.19.clone.1 = s32[12]{0:T(128)} "
                          "broadcast(s32[] %c)")["name"] == "broadcast"


def test_window_falls_back_to_the_session(tmp_path):
    path = tmp_path / "small.xplane.pb"
    path.write_bytes(gzip.decompress(SMALL.read_bytes()))
    t = trace.load(str(path))
    lo, hi = trace.window_of(t)
    t["host"] = {k: [e for e in v if e[0] != trace.WINDOW]
                 for k, v in t["host"].items()}
    s_lo, s_hi = trace.window_of(t)
    assert s_lo == 0 and s_lo <= lo < hi <= s_hi

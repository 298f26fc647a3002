"""The reduction from trace to numbers, on a small trace recorded on a
TPU v5e (the first events of PR 23's first traced run of the
batch-generation cell) and on hand-made intervals."""

import json
import os

import pytest

from harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as fh:
        return json.load(fh)


def test_union_and_gaps_by_hand():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert tr.union_seconds(spans) == pytest.approx(3.0)
    assert tr.gaps(spans, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert tr.union_seconds([]) == 0.0


def test_instruction_text_gives_name_opcode_and_shape():
    text = ("%paged_decode.5 = bf16[16,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
            "custom-call(s32[16,256]{1,0:T(8,128)S(1)} %copy-done.3, "
            "s32[16]{0:T(128)} %x), custom_call_target=tpu_custom_call")
    assert tr.parse_op(text) == {"op": "paged_decode.5",
                                 "opcode": "custom-call",
                                 "shape": "bf16[16,8,4,128]"}
    loop = "%while.3 = (s32[]{:T(128)}, bf16[16,1,4096]{2,0,1}) while(%tuple)"
    assert tr.parse_op(loop)["opcode"] == "while"
    assert tr.parse_op("jit_run(123)")["op"] == "jit_run(123)"


def test_recorded_trace_reduces(trace):
    planes = tr.device_planes(trace)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    busy = tr.busy(trace)
    assert 0 < busy["busy_s"] < busy["window_s"]
    # the while loop spans its body: left out of sums, kept in the union
    ops = tr.line_events(planes[0], tr.OPS_LINE)
    assert any(ev["opcode"] == "while" for ev in ops)
    assert all(ev["opcode"] != "while" for ev in tr.leaf_ops(planes[0]))
    total = sum(tr.op_seconds(trace).values())
    assert total <= busy["busy_s"] * 1.05
    seconds, calls = tr.seconds_matching(trace, r"^paged_decode")
    assert calls >= 1 and seconds > 0
    # an operand that mentions the kernel is not the kernel
    assert tr.seconds_matching(trace, r"^copy")[1] > 0
    assert all(ev["op"].startswith("paged_decode") for ev in
               tr.leaf_ops(planes[0]) if tr.re.search(r"^paged_decode",
                                                       ev["op"]))


def test_decode_program_is_the_one_that_runs_the_kernel(trace):
    mods = tr.modules_running(trace, r"^paged_decode")
    assert mods and all(m["name"].startswith("jit_") for m in mods)
    assert not tr.modules_running(trace, r"^no_such_kernel")


def test_breakdown_names_ops_and_gaps(trace):
    out = tr.breakdown(trace, top=5)
    assert len(out["device_ops"]) == 5 and len(out["idle_gaps"]) <= 5
    assert out["device_ops"][0][0].startswith("paged_decode")
    seconds = [s for _, s in out["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)
    for name, gap in out["idle_gaps"]:
        assert name.startswith("host:") and gap > 0
        assert " " not in name


def test_exposed_time_is_what_nothing_else_covers():
    def op(name, start, dur):
        return {"name": name, "start": start, "dur": dur, "op": name,
                "opcode": "fusion", "shape": ""}

    plane = {"name": "/device:TPU:0", "lines": [{"name": tr.OPS_LINE, "events": [
        op("all-to-all.1", 0.0, 1.0), op("fusion.1", 0.5, 1.0),
        op("all-to-all.2", 2.0, 1.0)]}]}
    assert tr.exposed_seconds({"planes": [plane]}, r"^all-to-all") == \
        pytest.approx(1.5)

"""The trace reduction, on hand-made intervals and on a small profile
recorded on a TPU v5e (five runs of a jitted 512x512 bf16 matmul; its
device plane, kept as XSpace text)."""

import os

import numpy as np
import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "tiny_v5e.xplane.pbtxt")


@pytest.mark.parametrize("starts,ends,want", [
    ([], [], 0.0),
    ([0], [10], 10.0),
    ([0, 20], [10, 25], 15.0),          # disjoint
    ([0, 5], [10, 8], 10.0),            # nested
    ([0, 5, 9], [6, 9, 12], 12.0),      # chained overlaps
    ([10, 0], [12, 11], 12.0),          # unsorted
])
def test_union_seconds(starts, ends, want):
    assert trace_reduce.union_seconds(starts, ends) == want


def _recorded():
    from jax.profiler import ProfileData

    with open(DATA) as f:
        return ProfileData.from_text_proto(f.read())


def _device_events(pd, line):
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ln = next(x for x in plane.lines if x.name == line)
    return [(e.name, e.start_ns, e.end_ns) for e in ln.events]


def test_recorded_profile_busy_and_modules():
    pd = _recorded()
    out = trace_reduce.reduce_profile(pd, chips=1,
                                      groups={"matmul": "jit__lambda"})
    ops = _device_events(pd, "XLA Ops")
    mods = _device_events(pd, "XLA Modules")
    assert len(ops) == 15 and len(mods) == 5
    # busy: sweep the ops in start order, merging overlaps by hand
    busy, cur = 0.0, None
    for _, s, e in sorted(ops, key=lambda x: x[1]):
        if cur is None or s > cur[1]:
            busy += 0 if cur is None else cur[1] - cur[0]
            cur = [s, e]
        else:
            cur[1] = max(cur[1], e)
    busy += cur[1] - cur[0]
    assert out["busy_s"] == pytest.approx(busy * 1e-9)
    assert out["chips_seen"] == 1
    assert out["modules"]["matmul"]["runs"] == 5
    assert out["modules"]["matmul"]["seconds"] == pytest.approx(
        sum(e - s for _, s, e in mods) * 1e-9)
    # the ops sum to their labels' totals; the matmul fusion dominates
    total = sum(e - s for _, s, e in ops) * 1e-9
    assert sum(out["ops"].values()) == pytest.approx(total)
    top = max(out["ops"], key=out["ops"].get)
    assert top.startswith("fusion fusion bf16[512,512]")
    # idle gaps lie between busy runs and add up with busy to the span
    gaps = out["gaps_ns"]
    assert np.all(gaps[:, 1] > gaps[:, 0])
    span = max(e for _, _, e in ops) - min(s for _, s, _ in ops)
    assert (gaps[:, 1] - gaps[:, 0]).sum() * 1e-9 + out["busy_s"] == \
        pytest.approx(span * 1e-9)
    assert out["span_s"] == pytest.approx(span * 1e-9)


def test_chips_beyond_the_cell_are_ignored():
    out = trace_reduce.reduce_profile(_recorded(), chips=0, groups={})
    assert out["chips_seen"] == 0 and out["busy_s"] == 0.0


def test_breakdown_without_a_tracer_names_the_host():
    out = trace_reduce.reduce_profile(_recorded(), chips=1, groups={})
    b = trace_reduce.breakdown(out, tracer=None, mark_perf=None)
    assert b["device_ops"][0][0].startswith("fusion")
    assert len(b["device_ops"]) == 3
    assert [name for name, _ in b["idle_gaps"]] == ["host"]
    assert b["idle_gaps"][0][1] == pytest.approx(
        out["span_s"] - out["busy_s"])


def test_op_label():
    text = ("%sort.27 = (f32[128,256206]{0,1:T(8,128)}, s32[128,256206]"
            "{0,1:T(8,128)}) sort(f32[128,256206]{0,1:T(8,128)} %fusion.310")
    assert trace_reduce.op_label(text) == "sort.27 sort (f32[128,256206]"

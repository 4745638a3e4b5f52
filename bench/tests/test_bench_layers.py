"""The layer readings (bench/layers.py) and the admit_idle_share reader,
on a hand-made profile in the v5e shape whose numbers are worked out
here by hand, and the script's run of a small cell on the CPU."""

import os
import subprocess
import sys
import types

import pytest

from bench import harness, layers, trace_reduce
from bench.tests.test_bench_harness import _tiny_root

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data",
                    "scoped_v5e.xplane.pbtxt")
NS = 1e-9


def _profile():
    from jax.profiler import ProfileData

    with open(DATA) as f:
        return ProfileData.from_text_proto(f.read())


# the compiled decode scan the fixture's ops come from, as
# compiled.as_text() prints it: one instruction per line, each with the
# op_name its scopes gave it (a copy the compiler put in has none)
DECODE_HLO = """HloModule jit__horizon, is_scheduled=true

%fused_computation.1 (param_0: bf16[128,1024]) -> bf16[128,1024] {
  %param_0 = bf16[128,1024]{1,0} parameter(0)
  ROOT %neg.1 = bf16[128,1024]{1,0} negate(bf16[128,1024]{1,0} %param_0), metadata={op_name="jit(_horizon)/while/body/dec.layers/while/body/dec.attn/neg"}
}

ENTRY %main.9 (p.1: bf16[128,1024]) -> f32[128,256206] {
  %while.1 = (s32[], f32[128,1024]{1,0}) while((s32[], f32[128,1024]{1,0}) %tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(_horizon)/while"}
  %fusion.1 = bf16[128,1024]{1,0:T(8,128)(2,1)} fusion(bf16[128,1024]{1,0} %p.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(_horizon)/while/body/dec.layers/while/body/dec.attn/dot_general"}
  %fusion.2 = bf16[128,1024]{1,0} fusion(bf16[128,1024]{1,0} %p.2), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(_horizon)/while/body/dec.layers/while/body/dec.cross/dot_general"}
  %sort.1 = (f32[128,256206]{1,0}, s32[128,256206]{1,0}) sort(f32[128,256206]{1,0} %p.3, s32[128,256206]{1,0} %iota.1), dimensions={1}, metadata={op_name="jit(_horizon)/while/body/closed_call/sampler/vmap()/sort"}
  %dynamic-slice.1 = s8[4224,8,16,64]{3,2,1,0} dynamic-slice(s8[12,4224,8,16,64]{4,3,2,1,0} %p.7, s32[] %i.1), dynamic_slice_sizes={1,4224,8,16,64}, metadata={op_name="jit(_horizon)/while/body/dec.layers/while/body/dynamic_slice"}
  %copy.1 = f32[128,1024]{1,0} copy(f32[128,1024]{1,0} %p.4)
  ROOT %fusion.3 = f32[128,256206]{1,0} fusion(bf16[128,1024]{1,0} %p.5), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(_horizon)/while/body/head/dot_general"}
}
"""
# the same scan at another length: other shapes, other scopes
OTHER_HLO = """ENTRY %main.3 (p.1: bf16[64,1024]) -> f32[64,256206] {
  %fusion.1 = bf16[64,1024]{1,0} fusion(bf16[64,1024]{1,0} %p.1), kind=kOutput, metadata={op_name="jit(_horizon)/while/body/dec.ffn/dot_general"}
  %copy.1 = f32[128,1024]{1,0} copy(f32[128,1024]{1,0} %p.4), metadata={op_name="jit(_horizon)/while/body/head/copy"}
}
"""


@pytest.mark.parametrize("stack,want", [
    ("jit(_horizon)/while/body/dec.attn/dot_general", "dec.attn"),
    ("jit(_horizon)/while/body/closed_call/sampler/vmap()/sort", "sampler"),
    ("jit(_prefill_paged)/while/body/dec.cross.kv/dot_general",
     "dec.cross.kv"),
    ("jit(_horizon)/dec.layers/while/body/dec.cross/dot_general",
     "dec.cross"),
    ("jit(_horizon)/dec.layers/while/body/dynamic_slice", "dec.layers"),
    ("jit(_horizon)/dec.attention/add", None),
    ("jit(_horizon)/while", None),
])
def test_scope_of(stack, want):
    assert layers.scope_of(stack) == want


def test_hlo_scopes_label_every_instruction():
    by_label = layers.hlo_scopes(DECODE_HLO)
    assert by_label["fusion.1 fusion bf16[128,1024]"] == "dec.attn"
    assert by_label["neg.1 negate bf16[128,1024]"] == "dec.attn"
    assert by_label["dynamic-slice.1 dynamic-slice s8[4224,8,16,64]"] == \
        "dec.layers"
    assert by_label["fusion.3 fusion f32[128,256206]"] == "head"
    assert by_label["copy.1 copy f32[128,1024]"] is None
    assert by_label["while.1 while (s32[],"] is None
    assert "param_0 parameter bf16[128,1024]" in by_label
    assert len(by_label) == 9


def test_scope_seconds_count_leaves_once():
    """Decode scans: the two `while` events hold their bodies' ops, so
    they add only their self time (900 - 870 and 800 - 600 ns); leaves
    and containers together come to the union of the ops. Each scan is
    read with the program whose labels it matches, whatever the order."""
    pd = _profile()
    progs = [layers.hlo_scopes(OTHER_HLO), layers.hlo_scopes(DECODE_HLO)]
    out = layers.scope_seconds(pd, 1, "jit__horizon", progs)
    assert out["runs"] == 2
    assert out["module_s"] == pytest.approx(2000 * NS)
    want = {"dec.attn": 590, "dec.cross": 200, "sampler": 600,
            "dec.layers": 80, "head": 100, "unscoped": 50}
    assert out["scopes"].keys() == want.keys()
    for k, v in want.items():
        assert out["scopes"][k] == pytest.approx(v * NS)
    assert out["containers"] == pytest.approx(230 * NS)
    assert out["unscoped_top"] == [["copy.1 copy f32[128,1024]",
                                    pytest.approx(50 * NS)]]
    assert out["matched"] == [8, 8]
    union = (1950 - 1000) + (3900 - 3000)
    assert sum(out["scopes"].values()) + out["containers"] == \
        pytest.approx(union * NS)
    assert layers.scope_seconds(pd, 1, "jit__horizon",
                                progs[::-1]) == out
    # without the compiled programs every leaf is unscoped
    bare = layers.scope_seconds(pd, 1, "jit__horizon")
    assert bare["scopes"] == {"unscoped": pytest.approx(1620 * NS)}
    assert bare["matched"] == [0, 8]
    pre = layers.scope_seconds(pd, 1, "jit__prefill_paged", progs)
    assert pre["scopes"] == {"unscoped": pytest.approx(400 * NS)}
    assert layers.scope_seconds(pd, 0, "jit__horizon")["runs"] == 0


def test_host_spans_and_idle_under_them():
    """Device idle: [1950, 3000) and [3900, 5000) ns. The two `admit`
    spans hold 1050 + 700 ns of it, their sub-spans 450 + 500 + 600."""
    pd = _profile()
    spans = layers.host_spans(pd)
    assert [n for n, _, _ in spans] == [
        "admit", "admit.group", "admit.prefill", "admit", "admit.first_sync",
        "dispatch"]
    gaps = trace_reduce.reduce_profile(pd, 1, {})["gaps_ns"]
    assert gaps.tolist() == [[1950, 3000], [3900, 5000]]
    idle = layers.idle_by_span(gaps, spans, layers.ENGINE_SPANS)
    want = {"admit": 1750, "admit.group": 450, "admit.prefill": 500,
            "admit.first_sync": 600, "dispatch": 100}
    for k in layers.ENGINE_SPANS:
        assert idle[k] == pytest.approx(want.get(k, 0) * NS)


@pytest.mark.parametrize("a,b,want", [
    ([], [(0, 10)], 0.0),
    ([(0, 10)], [(5, 20)], 5.0),
    ([(0, 10), (5, 12)], [(11, 30), (0, 1)], 2.0),      # merged first
    ([(20, 30), (0, 10)], [(8, 25)], 7.0),              # unsorted
])
def test_overlap_s(a, b, want):
    assert layers.overlap_s(a, b) == pytest.approx(want * NS)


def _run_with_admit(dt, spans_ns, chips_seen=1):
    """A finished traced run whose Tracer holds ``admit`` phases at the
    given profile times, laid on the host's clock through the mark: the
    mark at 1000 ns on the profile is perf_counter 105 s."""
    origin, t0 = 100.0, 5.0
    perf = lambda ns: origin + t0 + (ns - 1000) * NS  # noqa: E731
    events = [types.SimpleNamespace(ph="B", tid=0, name="round",
                                    ts_us=perf(1400) * 1e6, dur_us=0.0)]
    for a, b in spans_ns:
        events.append(types.SimpleNamespace(
            ph="X", tid=0, name="admit", ts_us=perf(a) * 1e6,
            dur_us=(b - a) * 1e-3))
    # the admission track's sub-spans are not phases
    events.append(types.SimpleNamespace(
        ph="X", tid=-1, name="admit.prefill", ts_us=perf(1950) * 1e6,
        dur_us=1.0))
    dt = dict(dt, chips_seen=chips_seen)
    return harness.Run(root=REPO, device_trace=dt,
                       tracer=types.SimpleNamespace(events=events),
                       origin=origin, trace_t0=t0, trace_t1=t0 + 1e-5)


def test_admit_idle_share_reads_the_tracer_on_the_profile_clock():
    dt = trace_reduce.reduce_profile(_profile(), 1, {})
    assert dt["mark_ns"] == 1000
    read = harness.load_reader(REPO, "admit_idle_share")
    run = _run_with_admit(dt, [(1500, 3200), (3850, 4600)])
    # 1750 ns of a 10 us window
    assert read(run) == pytest.approx(17.5, rel=1e-6)
    bulk = harness.load_reader(REPO, "admit_idle_share.bulk")
    assert bulk(run) == read(run)
    assert read(_run_with_admit(dt, [])) is None
    assert read(_run_with_admit(dt, [(1500, 3200)], chips_seen=0)) is None
    run.tracer = None
    assert read(run) is None


def test_the_script_reads_the_host_spans_of_a_small_cell(tmp_path):
    """The layers script runs a small cell traced on the CPU: the
    engine's phases and sub-spans are found on the profile's host
    plane (there is no device plane there, so no scope seconds)."""
    root = _tiny_root(tmp_path)
    out = layers._run("small-backlog", 5, 2.0, True, 0.0, root=root,
                      require_tpu=False)
    lay = out["layers"]
    for name in ("admit", "dispatch", "sync", "walk", "admit.group",
                 "admit.inputs", "admit.pages", "admit.prefill",
                 "admit.first_sync", "admit.install", "admit.emit"):
        assert lay["host_spans"][name] > 0, name
    assert lay["scopes"]["decode"]["runs"] == 0
    assert lay["engine_window"]["decode_syncs"] > 0
    assert out["result"]["correct"]
    untraced = layers._run("small-backlog", 5, 2.0, False, 0.0, root=root,
                           require_tpu=False, warm=False)
    assert "scopes" not in untraced["layers"]
    assert set(untraced["layers"]["engine_window"]) == set(
        lay["engine_window"])


def test_the_script_runs_from_a_checkout_and_needs_a_tpu(tmp_path):
    """Run as a script from the root of a checkout it finds the
    benchmark's modules, and without a TPU it exits non-zero with no
    result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "bench/layers.py", "--workload", "int4-bulk-backlog",
         "--seed", "1", "--seconds", "1"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stderr
    assert "not a TPU" in out.stderr and "{" not in out.stdout

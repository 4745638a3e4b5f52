"""The harness on the CPU: it finds configurations, traffic mixes and
metric readers by name, refuses to measure without a TPU, and a run
whose served tokens are altered where they are produced comes out not
correct."""

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

from bench import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SMALL = {"name": "small", "family": "encdec", "enc_layers": 2,
         "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "vocab_size": 512, "mlp_act": "relu",
         "tie_embeddings": True, "norm_eps": 1e-5, "rope_theta": 10000.0,
         "enc_len": 32}


def _tiny_root(tmp_path, limit=0.01):
    """A checkout of the benchmark with files of its own added by name:
    a small configuration, a small traffic mix and a metric reader."""
    root = tmp_path / "root"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "bench", "configs",
                           "nllb200-600m-int4.json")) as f:
        conf = json.load(f)
    conf["model"] = SMALL
    conf["deploy"] = {"slots": 8, "max_len": 48, "max_src_len": 32,
                      "paged": True, "page_size": 8, "horizon": 4}
    conf["check"] = {"gap_mean_limit": limit, "pad_src": 32, "pad_tgt": 32,
                     "control": "fp8"}
    (root / "bench" / "configs" / "small-int4.json").write_text(
        json.dumps(conf))
    mix = {"arrival": {"kind": "poisson", "rate_per_s": 6.0},
           "warm_s": 0.5, "drain_s": 20.0,
           "src_len": {"median": 8, "sigma": 0.6, "min": 2, "max": 32},
           "out_len": {"ratio_low": 0.8, "ratio_high": 1.3, "min": 2,
                       "max": 12},
           "vocab": {"low": 4, "high": 400}, "langs": {"low": 400,
                                                       "high": 500},
           "pool_seed": 1}
    (root / "bench" / "traffic" / "small-poisson.json").write_text(
        json.dumps(mix))
    backlog = dict(mix, arrival={"kind": "backlog", "pool_size": 24,
                                 "queued_min": 8}, drain_s=0.0)
    (root / "bench" / "traffic" / "small-backlog.json").write_text(
        json.dumps(backlog))
    (root / "bench" / "metrics" / "requests_served.py").write_text(
        "def read(run):\n"
        "    return sum(r.done for r in run.records)\n")
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "small-int4", "source": "test",
                     "file": "bench/configs/small-int4.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "small-poisson", "config": "small-int4",
                       "traffic": "small-poisson", "chips": 1,
                       "why": "test"},
                      {"name": "small-backlog", "config": "small-int4",
                       "traffic": "small-backlog", "chips": 1,
                       "why": "test"}],
        "end_to_end": [
            {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "requests_served", "unit": "requests",
             "better": "higher", "source": "host_clock", "layer": "test",
             "moves": "tokens_per_s"},
            {"name": "decode_step_ms", "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "model step",
             "moves": "tokens_per_s", "workloads": ["small-poisson"]}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def test_cell_metrics_follow_workloads_and_moves():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for cell in spec["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(spec, cell["name"],
                                                        False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = harness.cell_metrics(spec, cell["name"], True)
        assert per
        assert all(m["moves"] in e2e for m in per)
        for m in per + [{"name": n} for n in e2e]:
            assert os.path.exists(os.path.join(
                REPO, "bench", "metrics", m["name"] + ".py"))


def test_files_added_by_name_are_picked_up(tmp_path):
    """A configuration, a traffic mix and a metric reader that exist
    only as new files drive a whole run, traced, with no code change."""
    root = _tiny_root(tmp_path)
    res = harness.run_cell("small-poisson", 3, 2.0, True, root=root,
                           require_tpu=False)
    assert res["correct"], res["check_lines"]
    assert res["metrics"]["requests_served"]["value"] > 0
    # no device plane on the CPU: the device reader finds nothing and
    # its metric is left out, never reported as 0
    assert "decode_step_ms" not in res["metrics"]
    assert res["env"]["compiles_in_window"]["backend"] == 0
    assert res["attempted"] == 12 and res["failed"] == 0
    assert list(res)[-3:] == ["check", "env", "check_lines"]
    assert res["check"]["gap_mean"]["limit"] == 0.01
    e2e = harness.run_cell("small-poisson", 3, 2.0, False, root=root,
                           require_tpu=False, warm=False)
    assert set(e2e["metrics"]) == {"tokens_per_s", "setup_s"}


def test_a_token_altered_where_produced_is_not_correct(tmp_path,
                                                       monkeypatch):
    import repro.serving.engine as engine_mod

    real = engine_mod.sample_tokens_scan

    def altered(*args, **kw):
        tok = real(*args, **kw)
        return (tok + 1) % SMALL["vocab_size"]

    monkeypatch.setattr(engine_mod, "sample_tokens_scan", altered)
    root = _tiny_root(tmp_path)
    res = harness.run_cell("small-poisson", 4, 2.0, False, root=root,
                           require_tpu=False, warm=False)
    assert res["correct"] is False
    assert res["check"]["gap_mean"]["value"] > 0.01


def test_no_tpu_no_result(tmp_path):
    """Without a TPU, and in a directory holding only BENCHMARK.json and
    the benchmark's files, the run exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "bench/run.py", "--workload",
           "int4-sentences-poisson", "--seed", "1", "--seconds", "1",
           "--trace", "0"]
    out = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and "{" not in out.stdout
    assert "not a TPU" in out.stderr
    bare = tmp_path / "bare"
    shutil.copytree(os.path.join(REPO, "bench"), bare / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), bare)
    env.pop("PYTHONPATH", None)
    out = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and "{" not in out.stdout


def test_a_backlog_cell_runs(tmp_path):
    root = _tiny_root(tmp_path)
    res = harness.run_cell("small-backlog", 5, 2.0, False, root=root,
                           require_tpu=False)
    assert res["correct"], res["check_lines"]
    assert res["metrics"]["tokens_per_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["env"]["compiles_in_window"]["backend"] == 0
    assert res["env"]["precompiled"] == res["env"]["src_lengths"] + 3


class _FastEngine:
    """Admits every queued request into 4 slots and finishes it in the
    same round: a program far faster than any backlog sized in advance."""

    def __init__(self):
        self.queue, self.done, self.next_id = [], [], 0
        self.pending_at_round = []

    @property
    def num_pending(self):
        return len(self.queue)

    num_active = 0

    def submit(self, request, params, on_token):
        self.queue.append((self.next_id, params.max_new_tokens, on_token))
        self.next_id += 1
        return self.next_id - 1

    def serve_rounds(self):
        while self.queue:
            self.pending_at_round.append(len(self.queue))
            admitted, self.queue = self.queue[:4], self.queue[4:]
            for rid, n, on_token in admitted:
                for _ in range(n):
                    on_token(1)
                self.done.append(types.SimpleNamespace(
                    request_id=rid, finish_reason="length",
                    token_ids=[1] * n))
            yield

    def take_finished(self):
        out, self.done = self.done, []
        return out


def test_the_backlog_never_runs_dry():
    """However fast the program, the load generator keeps its queue at
    least ``queued_min`` deep, from the corpus sent again and again."""
    from bench import traffic

    mix = {"arrival": {"kind": "backlog", "pool_size": 20,
                       "queued_min": 8},
           "warm_s": 0.05, "drain_s": 0.0,
           "src_len": {"median": 8, "sigma": 0.6, "min": 2, "max": 32},
           "out_len": {"ratio_low": 0.8, "ratio_high": 1.3, "min": 2,
                       "max": 12},
           "vocab": {"low": 4, "high": 400},
           "langs": {"low": 400, "high": 500}, "pool_seed": 1}
    engine = _FastEngine()
    family = types.SimpleNamespace(request=lambda item: {})
    records, _, _ = harness.open_loop(
        engine, traffic.schedule(mix, 3, 0.2), family, 0.05, 0.25, 0.0,
        queued_min=8)
    assert len(records) > 10 * 20
    assert len(engine.pending_at_round) > 50
    assert min(engine.pending_at_round) >= 8
    assert sum(r.done for r in records) >= len(records) - 8


@pytest.mark.parametrize("name", ["nope"])
def test_unknown_workload(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    with pytest.raises(harness.BenchError):
        harness.find_cell(spec, name)


# A size at which the control separates from the program on the CPU:
# over seeds 1-8 the program's mean served gap read 1.3e-5 to 1.9e-4 and
# the fp8 control's 1.3e-3 to 3.4e-3 (the configurations' own limits
# come from their own readings; PERF.md).
CONTROL_SIZE = {"vocab_size": 16384, "d_model": 256, "num_heads": 4,
                "num_kv_heads": 4, "head_dim": 64, "d_ff": 1024}
CONTROL_LIMIT = 5e-4


@pytest.mark.parametrize("seed", [1, 6])
def test_the_control_is_not_correct(tmp_path, monkeypatch, seed):
    """The reference with fp8 activations, put in the program's place at
    the same prompts and served tokens, reads past the limit that the
    program's own streams keep."""
    monkeypatch.setitem(SMALL, "vocab_size", CONTROL_SIZE["vocab_size"])
    for k, v in CONTROL_SIZE.items():
        monkeypatch.setitem(SMALL, k, v)
    root = _tiny_root(tmp_path, limit=CONTROL_LIMIT)
    mix_path = os.path.join(root, "bench", "traffic", "small-poisson.json")
    with open(mix_path) as f:
        mix = json.load(f)
    mix["vocab"] = {"low": 4, "high": 16184}
    mix["langs"] = {"low": 16184, "high": 16384}
    with open(mix_path, "w") as f:
        json.dump(mix, f)
    res = harness.run_cell("small-poisson", seed, 2.0, False, root=root,
                           require_tpu=False, warm=False, control=True)
    assert res["correct"], res["check_lines"]
    assert res["check"]["gap_mean"]["value"] <= CONTROL_LIMIT
    assert res["check"]["control_gap_mean"]["value"] > CONTROL_LIMIT


def test_tokens_per_s_counts_the_rounds_between_the_window_edges():
    """The rate counts the tokens delivered between the edges the load
    generator met (each at a round's end) over the time between them,
    not over the nominal window."""
    read = harness.load_reader(REPO, "tokens_per_s")
    rec = types.SimpleNamespace(times=[9.9, 10.3, 10.3, 30.0, 50.4, 50.9])
    run = harness.Run(root=REPO, records=[rec], w0=10.0, w1=50.0,
                      t_open=10.2, t_close=50.5)
    assert read(run) == pytest.approx(4 / 40.3)
    assert harness.load_reader(REPO, "tokens_per_s.rate")(run) == read(run)

"""One run of one benchmark cell.

``BENCHMARK.json`` names the cell; everything the run needs is found by
name from there, so the harness holds nothing of any one cell:

* the configuration file (``configs[].file``): the program's model
  sizes, the quantization spec and deployment settings it is served
  with, the stored formats the yardstick counts with, the family whose
  module under ``bench/families/`` draws its weights, builds its
  requests and holds its plain reference, and the limit of the check;
* the traffic mix ``bench/traffic/<traffic>.json``, read by the one
  generator in ``bench/traffic.py``;
* one reader ``bench/metrics/<metric>.py`` per metric, end-to-end and
  per-layer alike, each a ``read(run)`` that returns a number, or None
  where it finds nothing to read.

A run: draw the weights on the device and deploy them; compile the
traffic's programs several at a time, then run every prefill and
decode scan shape the traffic can reach once, then the traffic itself
for the mix's ``warm_s``; measure ``--seconds`` of it (open loop: each
request is submitted when due, between scheduler rounds, and a backlog
is kept queued); let the requests due in the window finish; free the
program; check a sample of the served streams against the plain
reference.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Any, List, Optional

import numpy as np

from bench import counts, trace_reduce, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# requests checked against the reference per run: the longest finished
# one, and the rest drawn from the seed
CHECK_REQUESTS = 24
# threads that compile the traffic's programs at once in a cold set-up
COMPILE_WORKERS = 8


class BenchError(RuntimeError):
    """The run cannot measure this cell (no chip, a missing file)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def find_cell(spec: dict, name: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(cells)}")
    cell = cells[name]
    confs = {c["name"]: c for c in spec["configs"]}
    return cell, confs[cell["config"]]


def cell_metrics(spec: dict, cell: str, per_layer: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end ones, or its
    per-layer ones (those listing the cell, or, without a list, those
    that move one of its end-to-end metrics)."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not per_layer:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if ((cell in m["workloads"]) if "workloads" in m
                else (m["moves"] in moved))]


def load_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def load_family(root: str, family: str):
    path = os.path.join(root, "bench", "families", f"{family}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_family_" + family, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Record:
    """One request as the load generator saw it (host clock, seconds
    from the schedule's origin)."""

    __slots__ = ("item", "due", "submit", "times", "reason", "tokens", "rid")

    def __init__(self, item):
        self.item = item
        self.due = item.due_s
        self.submit = None
        self.times: List[float] = []       # delivery time of each token
        self.reason = None
        self.tokens = None
        self.rid = None

    @property
    def done(self) -> bool:
        return self.reason is not None


class Run:
    """What a finished run hands the metric readers."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._root = kw.get("root", ROOT)

    def metric(self, name: str):
        """Another reader's value, by name (a variant reads its base)."""
        return load_reader(self._root, name)(self)

    # -- views the readers share ---------------------------------------

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records if r.item.in_window]

    def window_tokens(self, t0: float, t1: float):
        """(position j, source length) of each token decoded (j >= 1)
        and delivered to the host in [t0, t1)."""
        js, srcs = [], []
        for r in self.records:
            if not r.times:
                continue
            t = np.asarray(r.times)
            j = np.nonzero((t >= t0) & (t < t1))[0]
            j = j[j >= 1]
            js.append(j)
            srcs.append(np.full(len(j), r.item.src_len))
        if not js:
            return np.zeros(0), np.zeros(0)
        return np.concatenate(js), np.concatenate(srcs)

    def decode_counts(self):
        """FLOPs and least bytes of the decode steps in the traced
        window, from the lengths served there."""
        j, src = self.window_tokens(self.trace_t0, self.trace_t1)
        m, q = self.conf["model"], self.conf["formats"]
        flops = float(np.sum(counts.token_flops(m, j, src)))
        nbytes = float(np.sum(counts.token_kv_bytes(m, q, j, src))
                       + self.trace_steps * counts.step_weight_bytes(m, q))
        return flops, nbytes


# ---------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------

def device_info(require_tpu: bool, chips: int, peaks_table: dict):
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu:
        if d.platform != "tpu":
            raise BenchError(f"JAX found platform {d.platform!r}, not a TPU")
        if len(devs) < chips:
            raise BenchError(f"the cell needs {chips} chips, JAX found "
                             f"{len(devs)}")
    if d.device_kind not in peaks_table:
        raise BenchError(f"device kind {d.device_kind!r} is not in "
                         f"bench/peaks.json")
    return devs[:chips], peaks_table[d.device_kind]


def use_compile_cache(root: str) -> str:
    """Keep JAX's persistent compilation cache in ``.jax_cache`` inside
    the checkout, whatever the environment names, with no size limit
    and every program in it however fast it compiled, so that only a
    cell's first run in a checkout compiles. The program is handed the
    same directory through ``JAX_COMPILATION_CACHE_DIR``, and its
    ``configure_compile_cache`` is what turns the cache on. Returns the
    directory."""
    import jax

    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    from repro.runtime import configure_compile_cache
    return configure_compile_cache()


def _compile_counter():
    """Counts compiles: persistent-cache lookups, hits and writes, and
    backend compiles (a persistent-cache hit counts as one too), with
    the seconds spent tracing, lowering, compiling and reading the
    cache."""
    import jax

    seen = {"lookups": 0, "hits": 0, "writes": 0, "backend": 0,
            "names": [], "seconds": {}}
    counted = {"compile_requests_use_cache": "lookups",
               "cache_hits": "hits", "cache_misses": "writes"}

    def on_event(event, **_):
        key = counted.get(event.rsplit("/", 1)[-1])
        if key is not None and event.startswith("/jax/compilation_cache/"):
            seen[key] += 1

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["backend"] += 1
            seen["names"].append(str(kw.get("fun_name")))
        name = event.rsplit("/", 1)[-1]
        if name in ("jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
                    "backend_compile_duration", "cache_retrieval_time_sec"):
            seen["seconds"][name] = seen["seconds"].get(name, 0.0) + duration

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return seen


def _deploy(conf: dict, family, key, trace: bool):
    from repro.configs.base import ModelConfig
    from repro.obs import TraceConfig
    from repro.serving import deploy

    cfg = ModelConfig(**conf["model"])
    raw = family.init_params(conf["model"], key)
    pipe = deploy(cfg, conf["spec"], params=raw, **conf["deploy"],
                  trace=TraceConfig(capacity=1 << 22) if trace else None)
    del raw
    return pipe


def _precompile(engine, lengths, horizon: int, workers: int) -> int:
    """Compile, on ``workers`` threads at once, the program's prefill of
    one request at each source length of ``lengths`` and its decode scan
    at each length it picks (powers of two up to ``horizon``), so that
    the warm-up after it finds them compiled rather than compiling them
    one at a time. Each is lowered from the engine's own jitted callable
    with the arguments its admission and dispatch pass, and compiled
    ahead of time; the persistent cache keeps what compiles. Where the
    engine has no such callables this does nothing, and the warm-up
    compiles on its own. Returns the programs compiled."""
    import concurrent.futures

    import jax
    import jax.numpy as jnp

    prefill = getattr(engine, "_prefill_paged_fn", None)
    make_scan = getattr(engine, "_make_horizon_fn", None)
    if prefill is None or make_scan is None or engine.mesh is not None:
        return 0
    lowered = []
    rows = jnp.zeros((1, engine.max_pages), jnp.int32)
    for n in lengths:
        feed = jnp.zeros((1, 1), jnp.int32)
        inputs = engine._group_inputs(
            [feed], [{"src_tokens": np.zeros((1, n), np.int32)}])
        lowered.append(prefill.lower(
            engine.params, inputs, jnp.ones((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), rows, engine.cache,
            jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), jnp.float32),
            jnp.stack([jax.random.PRNGKey(0)])))
    alive, rem, eos = engine._scan_masks()
    k = 1
    while k <= horizon:
        fn = engine._horizon_fns.setdefault(k, make_scan(k))
        lowered.append(fn.lower(
            engine.params, engine.cur, engine.cache, engine._temps,
            engine._top_ks, engine._top_ps, engine._keys, engine._offsets,
            alive, rem, eos, engine._poison_arr(k)))
        k *= 2
    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        list(pool.map(lambda low: low.compile(), lowered))
    return len(lowered)


def _warm_shapes(engine, family, mix: dict, conf: dict, lengths) -> int:
    """Run every shape the traffic can reach once, through the engine's
    own submit and rounds, so that nothing compiles once the traffic
    starts: one request at each source length of ``lengths`` (the
    traffic sends no two of one length in a row, so each prefills as a
    group of one); then each decode scan length the engine picks (powers
    of two up to its horizon), with chains grown past their prefill
    pages; then one scan dispatched ahead of a full horizon from the
    previous scan's carry. Returns the requests served."""
    from repro.serving import SamplingParams

    horizon = conf["deploy"]["horizon"]
    lang = mix["langs"]["low"]
    rng = np.random.default_rng(0)
    served = 0

    def burst(sizes, new):
        nonlocal served
        for src_len in sizes:
            item = traffic.Item(0.0, src_len, new, lang,
                                rng.integers(mix["vocab"]["low"],
                                             mix["vocab"]["high"], src_len,
                                             dtype=np.int32))
            engine.submit(family.request(item),
                          SamplingParams(max_new_tokens=new))
        for _ in engine.serve_rounds():
            pass
        served += len(engine.take_finished())

    burst(lengths, 2)
    k = 1
    while k <= horizon:
        burst(lengths[:1], k + 1)
        k *= 2
    # a scan dispatched ahead from the previous one's carry merges the
    # masks with eager ops of its own, the same for any scan length;
    # three pages' worth of tokens grow a chain one page at a time too
    page = conf["deploy"]["page_size"]
    burst(lengths[:1], max(horizon + 2, 3 * page))
    return served


def open_loop(engine, source, family, w0: float, w1: float,
              drain_s: float, queued_min: int = 0, on_open=None,
              on_close=None, on_round=None) -> tuple:
    """Serve the items of ``source`` open loop: each is submitted when
    due (seconds from the origin, which is now), between scheduler
    rounds, so a slow round makes the generator late and never sends
    less; with ``queued_min``, items that are due wait until fewer than
    that many requests are queued in the program, which keeps a backlog
    that deep and no deeper. ``on_open`` and ``on_close`` are called
    with the origin at ``w0`` and ``w1``; ``on_round`` with the time
    after every round. After the close the loop goes on, arrivals
    included, until the requests due in the window have finished or
    ``drain_s`` has passed. Returns the records of the items submitted
    (each with its submit time, request id, token delivery times on the
    host clock relative to the origin, finish reason and tokens), the
    origin, and the end relative to it."""
    from repro.serving import SamplingParams

    clock = time.perf_counter
    source = iter(source)
    nxt = next(source, None)
    records: List[Record] = []
    window_due: List[Record] = []
    by_rid = {}
    gen, state = None, "warm"
    origin = clock()
    while True:
        now = clock() - origin
        if state == "warm" and now >= w0:
            state = "window"
            if on_open is not None:
                on_open(origin)
        if state == "window" and now >= w1:
            state = "drain"
            if on_close is not None:
                on_close(origin)
        while nxt is not None and nxt.due_s <= now and (
                not queued_min or engine.num_pending < queued_min):
            rec = Record(nxt)
            rec.submit = clock() - origin
            rec.rid = engine.submit(
                family.request(nxt),
                SamplingParams(max_new_tokens=nxt.new_tokens),
                on_token=lambda tok, t=rec.times: t.append(clock()))
            by_rid[rec.rid] = rec
            records.append(rec)
            if nxt.in_window:
                window_due.append(rec)
            nxt = next(source, None)
        # every request due in the window has been submitted by now
        if state == "drain" and (now > w1 + drain_s
                                 or all(r.done for r in window_due)):
            break
        if gen is None:
            if not (engine.num_pending or engine.num_active):
                if nxt is not None:
                    wait = nxt.due_s - (clock() - origin)
                    time.sleep(min(max(wait, 0.0), 0.001))
                continue
            gen = engine.serve_rounds()
        try:
            next(gen)
        except StopIteration:
            gen = None
        for out in engine.take_finished():
            rec = by_rid[out.request_id]
            rec.reason = out.finish_reason
            rec.tokens = list(out.token_ids)
        if on_round is not None:
            on_round(clock() - origin)
    t_end = clock() - origin
    if gen is not None:
        gen.close()
    for r in records:
        r.times = [t - origin for t in r.times]
    return records, origin, t_end


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, require_tpu: bool = True,
             t_start: Optional[float] = None, warm: bool = True,
             control: bool = False) -> dict:
    """Run one cell; returns the result line's fields (plus the check
    lines under "check_lines" and the run's set-up facts under "env").
    ``warm=False`` skips the shape warm-up and ``control=True`` also
    reads the control's gap: both for ``bench/control.py``, which sets
    the check's limit, never for a measured run."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cell, conf_entry = find_cell(spec, workload)
    conf = load_json(os.path.join(root, conf_entry["file"]))
    mix = traffic.load_mix(cell["traffic"], os.path.join(root, "bench"))
    peaks_table = load_json(os.path.join(root, "bench", "peaks.json"))
    devs, peaks = device_info(require_tpu, cell["chips"],
                              peaks_table["devices"])
    family = load_family(root, conf["family"])

    cache_dir = use_compile_cache(root)
    compiles = _compile_counter()

    ss = np.random.SeedSequence(seed)
    key = jax.random.PRNGKey(int(ss.generate_state(1)[0] >> 1))
    source = traffic.schedule(mix, seed, seconds)
    backlog = mix["arrival"]["kind"] == "backlog"

    # seconds from the process's start to the end of each set-up phase
    phases = {"imports": time.perf_counter() - t_start}
    pipe = _deploy(conf, family, key, trace)
    engine = pipe.engine
    phases["deploy"] = time.perf_counter() - t_start
    lengths = traffic.src_lengths(mix, seconds)
    precompiled = warm_requests = 0
    if warm:
        precompiled = _precompile(engine, lengths, conf["deploy"]["horizon"],
                                  COMPILE_WORKERS)
        phases["precompile"] = time.perf_counter() - t_start
        warm_requests = _warm_shapes(engine, family, mix, conf, lengths)
        phases["warm_shapes"] = time.perf_counter() - t_start
    compiles_warm = {k: compiles[k] for k in
                     ("lookups", "hits", "writes", "backend")}
    compiles_warm["seconds"] = dict(compiles["seconds"])

    # ---- the open loop ----------------------------------------------
    w0 = mix["warm_s"]
    w1 = w0 + seconds
    clock = time.perf_counter
    trace_dir = mark_perf = setup_s = None
    trace_t = [None, None]
    # the window's edges as the loop met them, each at a round's end
    edges = [None, None]
    steps_at = [0, 0]
    marks = {}
    in_use = []

    def on_open(origin):
        nonlocal trace_dir, mark_perf, setup_s
        setup_s = clock() - t_start
        edges[0] = clock() - origin
        marks["c0"] = dict(compiles, names=list(compiles["names"]))
        engine.reset_metrics()
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            mark_perf = clock()
            with jax.profiler.TraceAnnotation(trace_reduce.MARK):
                pass
            trace_t[0] = mark_perf - origin
            steps_at[0] = engine.metrics().decode_steps

    def on_round(now):
        if w0 <= now < w1:
            in_use.append(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                              for d in devs))

    def on_close(origin):
        edges[1] = clock() - origin
        marks["engine"] = engine.metrics()
        marks["c1"] = dict(compiles, names=list(compiles["names"]))
        if trace:
            steps_at[1] = engine.metrics().decode_steps
            trace_t[1] = clock() - origin
            jax.profiler.stop_trace()

    records, origin, t_end = open_loop(
        engine, source, family, w0, w1, 0.0 if backlog else mix["drain_s"],
        mix["arrival"].get("queued_min", 0), on_open, on_close, on_round)
    window_due = [r for r in records if r.item.in_window]
    c0, c1 = marks["c0"], marks["c1"]

    process_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devs)
    tracer = engine.trace
    engine_window = marks["engine"]
    del engine, pipe
    gc.collect()

    run = Run(root=root, spec=spec, cell=cell, conf=conf, mix=mix,
              peaks=peaks, records=records, w0=w0, w1=w1, seconds=seconds,
              t_open=edges[0], t_close=edges[1],
              setup_s=setup_s, engine_window=engine_window,
              tracer=tracer, origin=origin,
              trace_t0=trace_t[0], trace_t1=trace_t[1],
              trace_steps=steps_at[1] - steps_at[0],
              device_trace=None, t_end=t_end)
    if trace:
        run.device_trace = trace_reduce.reduce_dir(
            trace_dir, len(devs), conf["modules"])
        trace_reduce.remove(trace_dir)

    # ---- correctness ------------------------------------------------
    check = check_streams(run, family, key, seed, control)

    # ---- the result -------------------------------------------------
    if backlog:
        judged = [r for r in records if r.done and r.times
                  and w0 <= r.times[-1] < w1]
    else:
        # a request still running when the drain ends is late, not
        # failed: the latency readers count it with the run's end
        judged = window_due
    failed = sum(r.done and r.reason != "length" for r in judged)
    metrics = {}
    for m in cell_metrics(spec, workload, per_layer=trace):
        value = load_reader(root, m["name"])(run)
        if value is None:
            if not trace:
                raise BenchError(f"end-to-end metric {m['name']} read "
                                 "nothing")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    # what the deployment holds while it serves: the most in use at a
    # round's end inside the window, after deploy and warm-up (the
    # process's peak, printed beside it, also counts the f32 weights
    # that deploy quantizes and the scratch of every program)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(in_use) if in_use else None}
    result = {"correct": check["correct"], "attempted": len(judged),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        dt = run.device_trace
        device["busy_s"] = dt["busy_s"]
        device["window_s"] = run.trace_t1 - run.trace_t0
        result["breakdown"] = trace_reduce.breakdown(dt, tracer, mark_perf)
    result["check"] = check["numbers"]
    result["env"] = {
        "cache_dir": cache_dir,
        "peak_bytes_in_use": process_peak,
        "page_utilization": engine_window.page_utilization,
        "occupancy": engine_window.occupancy,
        "src_lengths": len(lengths),
        "precompiled": precompiled,
        "setup_phases": phases,
        "compiles_setup": compiles_warm,
        "compiles_in_window": {"lookups": c1["lookups"] - c0["lookups"],
                               "backend": c1["backend"] - c0["backend"],
                               "names": c1["names"][len(c0["names"]):]},
        "warm_requests": warm_requests,
        "requests_submitted": len(records),
        "unfinished_at_end": sum(not r.done for r in window_due),
        "tracer_dropped": tracer.dropped if tracer is not None else None,
    }
    result["env"]["widest"] = check.get("widest")
    result["env"]["control_widest"] = check.get("control_widest")
    result["check_lines"] = check["lines"]
    return result


def check_streams(run: Run, family, key, seed: int,
                  control: bool = False) -> dict:
    """Score a sample of the finished requests (the longest among them,
    the rest drawn from the seed) with the reference: at every served
    position, by how much the served token's logit lies below the
    reference's best. Compared: the mean of that gap over the sample's
    served tokens, and that every sampled stream ran to its budget. The
    widest gap is printed beside them. With ``control``, the same
    readings for the tokens the control would put first."""
    conf = run.conf
    chk = conf["check"]
    done = [r for r in run.records if r.done and r.tokens]
    if not done:
        return {"correct": False, "numbers": {},
                "lines": ["no request finished"]}
    rng = np.random.default_rng([seed, 7])
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + [int(i) for i in rng.choice(
        rest, min(len(rest), CHECK_REQUESTS - 1), replace=False)]
    W = family.prepare(family.init_params(conf["model"], key),
                       conf["formats"])
    gaps, ctrl, short = [], [], 0
    for i in pick:
        r = done[i]
        short += not (r.reason == "length"
                      and len(r.tokens) == r.item.new_tokens)
        args = (W, conf["model"], conf["formats"], r.item.src, r.item.lang,
                r.tokens, chk["pad_src"], chk["pad_tgt"])
        gaps.append(family.served_gaps(*args))
        if control:
            ctrl.append(family.served_gaps(*args, control=chk["control"]))
    del W
    gaps = np.concatenate(gaps)
    limit = chk["gap_mean_limit"]
    mean = float(gaps.mean())
    numbers = {"gap_mean": {"value": mean, "limit": limit},
               "streams_short": {"value": short, "limit": 0}}
    lines = [f"gap_mean {mean:.6g} limit {limit} ({len(pick)} requests, "
             f"{gaps.size} served tokens; widest gap {gaps.max():.6g})",
             f"streams_short {short} limit 0"]
    if control:
        ctrl = np.concatenate(ctrl)
        numbers["control_gap_mean"] = {"value": float(ctrl.mean()),
                                       "limit": limit}
        lines.append(f"control_gap_mean {ctrl.mean():.6g} limit {limit} "
                     f"(widest gap {ctrl.max():.6g})")
    return {"correct": mean <= limit and short == 0, "numbers": numbers,
            "lines": lines, "widest": float(gaps.max()),
            "control_widest": float(ctrl.max()) if control else None}


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    env = res.pop("env")
    lines = res.pop("check_lines")
    print("env " + json.dumps({**res["device"], **env}), flush=True)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0

"""Layer readings from a JAX profile: the program's own host spans and
the device seconds of each named scope.

The program mirrors its scheduler phases (``admit``, ``dispatch``,
``sync``, ``walk``) and the admission's sub-spans (``admit.*``) into the
profiler as ``TraceAnnotation`` s, which land on a host plane of the
profile on the same clock as the device's operations. Its jitted
programs name their layers with ``jax.named_scope`` (:data:`SCOPES`),
which the compiler keeps in each instruction's ``op_name`` metadata. A
TPU v5e profile, as ``jax.profiler.ProfileData`` reads it, gives an
operation's event its instruction's text for a name and no name stack
(its stats are ``device_offset_ps``, ``device_duration_ps`` and ``Time
Scale Multiplier``), so the scopes are read from the compiled programs'
text (``compiled.as_text()``) and matched to the events by label (name,
opcode and result type, as ``trace_reduce.op_label`` gives it).

Functions:

* :func:`host_spans`: the engine's annotations, as (name, start_ns,
  end_ns), on the profile's clock;
* :func:`hlo_scopes`: label -> scope of one compiled program;
* :func:`scope_seconds`: device seconds per scope inside one executable
  group (``jit__horizon``: the decode scans), counting leaf operations
  only. A ``while``, ``conditional`` or ``call`` operation's event
  spans the operations of its body, so it counts only its self time
  (its span less the union of the operations inside it), as
  ``containers``, and nothing counts twice;
* :func:`overlap_s`: how long two sets of intervals overlap, which
  lays the device's idle gaps on the spans the host was in.

Run as a script, it runs one cell as ``bench/run.py`` does, keeps the
profile long enough to read these too, and prints the result line
followed by a ``layers`` line::

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1] [--json <path>]

Both lines carry ``tokens_per_s`` and the engine's counters over the
window, so a traced and an untraced run of one seed can be compared.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from typing import (Dict, Iterable, List, Optional, Sequence,  # noqa: E402
                    Tuple)

if __name__ == "__main__":      # a script, from the root of a checkout
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:1] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from bench.trace_reduce import (DEVICE_PLANE, MODULES_LINE,  # noqa: E402
                                OPS_LINE, _merged, op_label,
                                union_seconds)

__all__ = ["SCOPES", "CONTAINERS", "ENGINE_SPANS", "scope_of", "hlo_scopes",
           "host_spans", "scope_seconds", "overlap_s", "idle_by_span"]

# the program's layer scopes, as the models and the sampler name them
SCOPES: Tuple[str, ...] = ("embed", "enc.attn", "enc.ffn", "dec.cross.kv",
                           "dec.layers", "dec.attn", "dec.cross", "dec.ffn",
                           "head", "sampler")
# operations whose event spans the operations of their bodies
CONTAINERS: Tuple[str, ...] = ("while", "conditional", "call")
# the engine's annotations: its scheduler phases and admission sub-spans
ENGINE_SPANS: Tuple[str, ...] = (
    "admit", "dispatch", "sync", "walk", "admit.faults", "admit.expire",
    "admit.group", "admit.inputs", "admit.pages", "admit.prefill",
    "admit.first_sync", "admit.install", "admit.emit")
# how many of the costliest unscoped labels scope_seconds names
TOP = 12

_SCOPE = re.compile(r"(?:^|/)(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?=/|$)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(name_stack: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` in a ``/``-separated name stack
    (``jit(_horizon)/while/body/dec.attn/dot_general`` -> ``dec.attn``),
    or None."""
    found = _SCOPE.findall(name_stack)
    return found[-1] if found else None


def hlo_scopes(hlo_text: str) -> Dict[str, Optional[str]]:
    """Label (``op_label``) -> scope of each instruction of a compiled
    program's HLO text, whose instructions carry their
    ``metadata={op_name=...}`` (None where none of :data:`SCOPES` is in
    it, or it has no op_name: an instruction the compiler put in)."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        text = line.strip()
        if not text.startswith(("%", "ROOT %")) or " = " not in text:
            continue
        m = _OP_NAME.search(text)
        out[op_label(text[5:] if text.startswith("ROOT") else text)] = \
            scope_of(m.group(1)) if m else None
    return out


def _opcode(label: str) -> str:
    parts = label.split(" ")
    return parts[1] if len(parts) > 2 else ""


def _runs(intervals) -> np.ndarray:
    """Disjoint sorted runs covering the union of (start, end) rows."""
    a = np.asarray(intervals, np.float64).reshape(-1, 2)
    if not len(a):
        return a
    return np.stack(_merged(a[:, 0], a[:, 1]), axis=1)


def overlap_s(a, b) -> float:
    """Seconds in both the union of intervals ``a`` and that of ``b``,
    each a sequence of (start_ns, end_ns)."""
    x, y = _runs(a), _runs(b)
    i = j = 0
    total = 0.0
    while i < len(x) and j < len(y):
        lo = max(x[i, 0], y[j, 0])
        hi = min(x[i, 1], y[j, 1])
        if hi > lo:
            total += hi - lo
        if x[i, 1] < y[j, 1]:
            i += 1
        else:
            j += 1
    return total * 1e-9


def host_spans(pd, names: Iterable[str] = ENGINE_SPANS
               ) -> List[Tuple[str, int, int]]:
    """The events named in ``names`` on the profile's host planes, as
    (name, start_ns, end_ns) in start order. A name may carry the
    annotation's arguments after a ``#``; they are dropped."""
    want = set(names)
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name in want:
                    out.append((name, ev.start_ns, ev.end_ns))
    out.sort(key=lambda t: t[1])
    return out


def idle_by_span(gaps_ns, spans: Sequence[Tuple[str, int, int]],
                 names: Iterable[str]) -> Dict[str, float]:
    """Seconds of the device's idle gaps that fall inside each named
    host span (spans of one name are merged first)."""
    return {n: overlap_s(gaps_ns, [(a, b) for m, a, b in spans if m == n])
            for n in names}


def scope_seconds(pd, chips: int, pattern: str,
                  programs: Sequence[Dict[str, Optional[str]]] = ()
                  ) -> dict:
    """Device seconds inside the executables whose name contains
    ``pattern``, averaged over the chips seen: ``module_s`` (their own
    events), ``runs``, ``scopes`` (leaf operations' seconds by scope,
    ``unscoped`` for the rest), ``containers`` (control operations'
    self time), ``unscoped_top`` (the costliest unscoped labels) and
    ``matched`` (leaf operations whose label a program holds, of all).
    ``programs`` holds :func:`hlo_scopes` of each compiled program the
    group may run (one per scan length); each executable, by name, is
    read with the one whose labels its operations match most often."""
    scopes: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    module_s = containers = 0.0
    runs = seen = matched = leaves = 0
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None or int(m.group(1)) >= chips:
            continue
        seen += 1
        mods, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                              for ev in line.events if pattern in ev.name)
            elif line.name == OPS_LINE:
                ops = list(line.events)
        runs += len(mods)
        module_s += sum(b - a for a, b, _ in mods) * 1e-9
        m_start = [a for a, _, _ in mods]
        inside = []                     # (start, end, label, module name)
        for ev in ops:
            k = bisect.bisect_right(m_start, ev.start_ns) - 1
            if k >= 0 and ev.end_ns <= mods[k][1]:
                inside.append((ev.start_ns, ev.end_ns, op_label(ev.name),
                               mods[k][2]))
        inside.sort(key=lambda t: (t[0], -t[1]))
        best = {}
        for name in {t[3] for t in inside}:
            labels = [t[2] for t in inside if t[3] == name]
            best[name] = max(programs, default={}, key=lambda p: sum(
                lab in p for lab in labels))
        for i, (a, b, label, name) in enumerate(inside):
            dur = (b - a) * 1e-9
            if _opcode(label) in CONTAINERS:
                cs, ce = [], []
                j = i + 1
                while j < len(inside) and inside[j][0] < b:
                    if inside[j][1] <= b:
                        cs.append(inside[j][0])
                        ce.append(inside[j][1])
                    j += 1
                containers += max(dur - union_seconds(cs, ce) * 1e-9, 0.0)
                continue
            leaves += 1
            matched += label in best[name]
            scope = best[name].get(label)
            if scope is None:
                scope = "unscoped"
                unscoped[label] = unscoped.get(label, 0.0) + dur
            scopes[scope] = scopes.get(scope, 0.0) + dur
    n = max(seen, 1)
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:TOP]
    return {"module_s": module_s / n, "runs": runs / n,
            "scopes": {k: v / n for k, v in sorted(scopes.items())},
            "containers": containers / n,
            "unscoped_top": [[k, v / n] for k, v in top],
            "matched": [matched, leaves], "chips_seen": seen}


# ---------------------------------------------------------------------
# the script
# ---------------------------------------------------------------------

def _decode_programs(engine) -> List[Dict[str, Optional[str]]]:
    """:func:`hlo_scopes` of the engine's decode scan at each length it
    has compiled (read back from the compile cache)."""
    alive, rem, eos = engine._scan_masks()
    return [hlo_scopes(fn.lower(
        engine.params, engine.cur, engine.cache, engine._temps,
        engine._top_ks, engine._top_ps, engine._keys, engine._offsets,
        alive, rem, eos, engine._poison_arr(k)).compile().as_text())
        for k, fn in sorted(engine._horizon_fns.items())]


def _run(workload: str, seed: int, seconds: float, trace: bool,
         t_start: float, **kw) -> dict:
    """One traced (or untraced) run through the harness, with the extra
    readings taken from the same profile before the harness removes it
    and from the programs it compiles ahead of time. ``kw`` goes to
    ``harness.run_cell``."""
    from bench import harness, trace_reduce

    extra: dict = {}
    programs: List[Dict[str, Optional[str]]] = []

    real_precompile = harness._precompile
    real_reduce_dir = trace_reduce.reduce_dir
    real_run = harness.Run

    def precompile(engine, lengths, horizon, workers):
        n = real_precompile(engine, lengths, horizon, workers)
        if trace:
            try:
                programs.extend(_decode_programs(engine))
            except Exception as e:  # noqa: BLE001 - a reading, not the run
                extra["programs_error"] = repr(e)
        return n

    def reduce_dir(trace_dir, chips, groups):
        from jax.profiler import ProfileData

        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise RuntimeError(f"no profile written under {trace_dir}")
        pd = ProfileData.from_file(files[0])
        out = trace_reduce.reduce_profile(pd, chips, groups)
        spans = host_spans(pd)
        extra["host_spans"] = {n: sum(1 for m, _, _ in spans if m == n)
                               for n in ENGINE_SPANS}
        idle = idle_by_span(out["gaps_ns"], spans, ENGINE_SPANS)
        extra["idle_by_host_span_s"] = idle
        extra["idle_s"] = float(np.sum(out["gaps_ns"][:, 1]
                                       - out["gaps_ns"][:, 0]) * 1e-9)
        extra["scopes"] = {"decode": scope_seconds(
            pd, chips, groups["decode"], programs)}
        return out

    class Run(real_run):
        def __init__(self, **kw):
            super().__init__(**kw)
            m = kw["engine_window"]
            extra["engine_window"] = {
                "decode_steps": m.decode_steps,
                "decode_syncs": m.decode_syncs,
                "synced_tokens": m.synced_tokens,
                "phase_admit_ms": m.phase_admit_ms}
            extra["trace_steps"] = kw["trace_steps"]
            extra["tokens_per_s"] = self.metric("tokens_per_s")

    harness._precompile = precompile
    trace_reduce.reduce_dir = reduce_dir
    harness.Run = Run
    try:
        res = harness.run_cell(workload, seed, seconds, trace,
                               t_start=t_start, **kw)
    finally:
        harness._precompile = real_precompile
        trace_reduce.reduce_dir = real_reduce_dir
        harness.Run = real_run
    if trace and "scopes" in extra:
        steps = extra["trace_steps"]
        window = res["device"]["window_s"]
        dec = extra["scopes"].get("decode")
        if dec and steps:
            extra["decode_ms_per_step"] = {
                k: v / steps * 1e3 for k, v in dec["scopes"].items()}
            leaf = sum(dec["scopes"].values())
            extra["decode_scoped_share"] = (
                (leaf - dec["scopes"].get("unscoped", 0.0))
                / dec["module_s"] if dec["module_s"] else None)
        extra["idle_by_host_span_pct"] = {
            k: v / window * 100 for k, v in
            extra["idle_by_host_span_s"].items()}
    return {"result": res, "layers": extra}


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--json", default=None,
                    help="also write both lines to this file")
    args = ap.parse_args(argv)
    from bench.harness import BenchError

    try:
        out = _run(args.workload, args.seed, args.seconds, bool(args.trace),
                   t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    res = out["result"]
    res.pop("check_lines")
    print(json.dumps(res), flush=True)
    print("layers " + json.dumps(out["layers"]), flush=True)
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=T_START))

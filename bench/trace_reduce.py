"""From a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX. On a TPU
each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per operation run on the chip, and whose line ``XLA Modules``
holds one event per executable run, named after the jitted function
(``jit__horizon(...)``). Host threads are planes of their own.

This module reduces that to:

* ``busy_s``: the union of the chip's operation intervals, averaged
  over the chips used;
* ``span_s``: first operation start to last operation end;
* ``modules``: device seconds and executions of each executable group
  the configuration names (``{"decode": "_horizon", ...}``: a group
  holds every module whose name contains the pattern);
* ``ops``: device seconds per operation name, summed;
* ``gaps``: the idle intervals between the busy ones, on the
  profiler's clock, with the offset that maps the host's
  ``time.perf_counter`` onto it (from a ``bench.mark`` annotation the
  harness emits), so that each gap can be laid beside what the host
  was doing.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from typing import Dict, Optional

import numpy as np

__all__ = ["union_seconds", "reduce_profile", "reduce_dir", "breakdown",
           "remove", "MARK"]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MARK = "bench.mark"


def union_seconds(starts, ends) -> float:
    """Length of the union of intervals [start, end) (any unit in,
    the same unit out)."""
    if len(starts) == 0:
        return 0.0
    order = np.argsort(starts, kind="stable")
    s = np.asarray(starts, np.float64)[order]
    e = np.asarray(ends, np.float64)[order]
    reach = np.maximum.accumulate(e)
    # an interval opens a new busy run where it starts past every
    # earlier interval's end
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    run_id = np.cumsum(new) - 1
    run_start = s[new]
    run_end = np.zeros(len(run_start))
    np.maximum.at(run_end, run_id, reach)
    return float(np.sum(run_end - run_start))


def _merged(starts, ends):
    """The busy runs themselves, as (starts, ends) arrays."""
    order = np.argsort(starts, kind="stable")
    s = np.asarray(starts, np.float64)[order]
    e = np.asarray(ends, np.float64)[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    run_id = np.cumsum(new) - 1
    run_end = np.zeros(int(run_id[-1]) + 1)
    np.maximum.at(run_end, run_id, reach)
    return s[new], run_end


_HLO_OP = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_label(text: str) -> str:
    """A short label for an ``XLA Ops`` event, whose name is the HLO
    instruction's text: its name, opcode and result type."""
    if " = " not in text:
        return text[:80]
    name, rest = text.split(" = ", 1)
    m = _HLO_OP.search(rest)
    return f"{name.lstrip('%')} {m.group(1) if m else '?'} " \
        f"{rest.split(' ', 1)[0].split('{', 1)[0]}"


def _module_group(name: str, groups: Dict[str, str]) -> Optional[str]:
    for group, pattern in groups.items():
        if pattern in name:
            return group
    return None


def reduce_profile(pd, chips: int, groups: Dict[str, str]) -> dict:
    """Reduce a loaded ``ProfileData`` (see module docstring)."""
    busy, spans = [], []
    modules = {g: {"seconds": 0.0, "runs": 0} for g in groups}
    ops: Dict[str, float] = {}
    gaps = None
    mark_ns = None
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARK and mark_ns is None:
                        mark_ns = ev.start_ns
            continue
        if int(m.group(1)) >= chips:
            continue
        starts, ends = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    starts.append(ev.start_ns)
                    ends.append(ev.end_ns)
                    label = op_label(ev.name)
                    ops[label] = ops.get(label, 0.0) + ev.duration_ns * 1e-9
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    g = _module_group(ev.name, groups)
                    if g is not None:
                        modules[g]["seconds"] += ev.duration_ns * 1e-9
                        modules[g]["runs"] += 1
        if not starts:
            continue
        busy.append(union_seconds(starts, ends) * 1e-9)
        spans.append((min(starts), max(ends)))
        if gaps is None:
            rs, re_ = _merged(starts, ends)
            gaps = np.stack([re_[:-1], rs[1:]], axis=1)   # ns
    n = max(len(busy), 1)
    for g in modules.values():
        g["seconds"] /= n
        g["runs"] = g["runs"] / n
    return {
        "busy_s": float(sum(busy) / n) if busy else 0.0,
        "span_s": float(np.mean([(b - a) * 1e-9 for a, b in spans]))
        if spans else 0.0,
        "chips_seen": len(busy),
        "modules": modules,
        "ops": {k: v / n for k, v in ops.items()},
        "gaps_ns": gaps if gaps is not None else np.zeros((0, 2)),
        "mark_ns": mark_ns,
    }


def reduce_dir(trace_dir: str, chips: int, groups: Dict[str, str]) -> dict:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no profile written under {trace_dir}")
    return reduce_profile(ProfileData.from_file(files[0]), chips, groups)


def breakdown(dt: dict, tracer, mark_perf: Optional[float],
              top: int = 10) -> dict:
    """The device operations that took most time, and the device's idle
    time summed by what the host was doing meanwhile (the engine's
    scheduler phases from its Tracer, else "host"), each as
    [name, seconds], at most ``top`` of each."""
    ops = sorted(dt["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle: Dict[str, float] = {}
    gaps = dt["gaps_ns"]
    phases = []
    if tracer is not None and dt["mark_ns"] is not None \
            and mark_perf is not None:
        for ev in tracer.events:
            if ev.ph == "X" and ev.tid == 0:
                phases.append((ev.ts_us * 1e-6, (ev.ts_us + ev.dur_us) * 1e-6,
                               ev.name))
    phases.sort()
    p_start = np.array([p[0] for p in phases])
    for a, b in gaps:
        dur = (b - a) * 1e-9
        name = "host"
        if phases:
            # the gap's middle on the host's perf_counter clock
            mid = mark_perf + ((a + b) / 2 - dt["mark_ns"]) * 1e-9
            i = int(np.searchsorted(p_start, mid, side="right")) - 1
            if i >= 0 and phases[i][1] >= mid:
                name = phases[i][2]
            else:
                name = "outside the scheduler"
        idle[name] = idle.get(name, 0.0) + dur
    idle_top = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, float(v)] for k, v in ops],
            "idle_gaps": [[k, float(v)] for k, v in idle_top]}


def remove(trace_dir: str) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)

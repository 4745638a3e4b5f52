"""Knee sweep: the highest Poisson rate a cell sustains without a
growing backlog, found once on the chip when a rate cell is defined.

    python3 bench/sweep.py --workload <cell> --seconds 30 --rates 8 10 12

One process deploys the cell, warms it up as a run does, then offers
each rate in turn (the cell's mix with only the rate changed) for the
mix's warm-up plus ``--seconds``, and reads over that window how fast
the queue of waiting requests grew (a least-squares slope, requests per
second), the requests and tokens completed per second, and the p95
time to first token. Whatever is left when a rate's window closes is
aborted before the next. One JSON line per rate. The benchmark's own
runs never run this; the rate chosen goes into the cell's traffic file
as a number.
"""

import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

from bench import harness, traffic  # noqa: E402


def sweep(workload, seconds, rates, seed, root=ROOT, require_tpu=True):
    """Offer each rate in turn; yields one dict of readings per rate."""
    import jax

    spec = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    cell, conf_entry = harness.find_cell(spec, workload)
    conf = harness.load_json(os.path.join(root, conf_entry["file"]))
    mix = traffic.load_mix(cell["traffic"], os.path.join(root, "bench"))
    harness.device_info(require_tpu, cell["chips"], harness.load_json(
        os.path.join(root, "bench", "peaks.json"))["devices"])
    family = harness.load_family(root, conf["family"])
    harness.use_compile_cache(root)

    t0 = time.perf_counter()
    pipe = harness._deploy(conf, family, jax.random.PRNGKey(seed), False)
    engine = pipe.engine
    mixes = []
    for rate in rates:
        m = copy.deepcopy(mix)
        m["arrival"]["rate_per_s"] = rate
        mixes.append(m)
    lengths = sorted(set().union(*(traffic.src_lengths(m, seconds)
                                   for m in mixes)))
    harness._precompile(engine, lengths, conf["deploy"]["horizon"],
                        harness.COMPILE_WORKERS)
    harness._warm_shapes(engine, family, mix, conf, lengths)
    yield {"setup_s": time.perf_counter() - t0, "src_lengths": len(lengths)}
    for rate, m in zip(rates, mixes):
        w0, w1 = m["warm_s"], m["warm_s"] + seconds
        samples = []

        def on_round(now):
            if w0 <= now < w1:
                samples.append((now, engine.num_pending))

        records, _, _ = harness.open_loop(
            engine, traffic.schedule(m, seed, seconds), family, w0, w1, 0.0,
            on_round=on_round)
        for r in records:
            if r.rid is not None and not r.done:
                engine.abort(r.rid)
        for _ in engine.serve_rounds():
            pass
        engine.take_finished()
        t, q = (np.array(x, np.float64) for x in zip(*samples)) \
            if samples else (np.zeros(1), np.zeros(1))
        slope = float(np.polyfit(t, q, 1)[0]) if len(t) > 2 else 0.0
        win = [r for r in records if r.item.in_window]
        done = [r for r in records if r.done and r.times
                and w0 <= r.times[-1] < w1]
        firsts = [(r.times[0] if r.times else w1) - r.due for r in win]
        toks = sum(sum(w0 <= x < w1 for x in r.times) for r in records)
        yield {"rate_per_s": rate, "backlog_slope_per_s": slope,
               "pending_first": int(q[0]), "pending_last": int(q[-1]),
               "completed_per_s": len(done) / seconds,
               "tokens_per_s": toks / seconds,
               "ttft_p95_ms": float(np.percentile(firsts, 95)) * 1e3,
               "due_in_window": len(win)}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for row in sweep(args.workload, args.seconds, args.rates, args.seed):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

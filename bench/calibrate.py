"""Readings that a cell's limits for ``correct`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 101-112 \\
        --control-seeds 101-103 [--out readings.jsonl]

One process builds the cell's engine once.  For each seed it gives the
engine that seed's weights, serves the seed's first job (every slot
busy, as in the window), and compares the finished
requests that a run would compare (:mod:`bench.check`) with the float32
reference: the program's widest and mean logit gaps.  For each control
seed it also reads the control's on the same requests: the float8
reference put in the program's place.  Where the cell has limits
(``limits/<cell>.json``), both go through the verdict a run gives
(:func:`bench.check.correct`): ``program_correct`` and
``control_correct``.  One JSON line per seed goes to standard output (and
to ``--out``); the last line on standard error says on which seeds the
control came out correct, which a sound limit never lets it.  The
benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


#: a routing margin under this is a near tie (probabilities of the k-th
#: and the next expert)
NEAR_TIE = 0.003


def _summary(who: str, gaps) -> dict:
    import numpy as np
    return {f"{who}_gap": float(gaps.max()),
            f"{who}_mean": float(gaps.mean()),
            f"{who}_p99": float(np.percentile(gaps, 99)),
            f"{who}_p90": float(np.percentile(gaps, 90)),
            f"{who}_p50": float(np.percentile(gaps, 50))}


def _seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np
    from bench import check, system
    from bench.spec import Bench
    from bench.weights import dims_of
    from repro.launch.compile_cache import use_compile_cache

    bench = Bench(ROOT)
    wl = bench.workload(args.workload)
    cfgj = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    gen = bench.generator(mix["generator"])
    geometry, dm = cfgj["engine"], dims_of(cfgj)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print("calibration runs on the chip only", file=sys.stderr)
        return 2
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    limits = bench.limits(args.workload)
    control_verdicts = []
    seeds = _seeds(args.seeds)
    controls = set(_seeds(args.control_seeds))
    _, engine = system.build(cfgj, seeds[0], devices[:wl["chips"]])
    shardings = jax.tree.map(lambda a: a.sharding, engine.params)
    out = open(args.out, "a") if args.out else None
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        if n:
            engine.params = system.weights(cfgj, engine.model.cfg, seed,
                                           shardings)
        job = next(gen.jobs(mix, geometry, dm.vocab, seed))
        served = engine.serve(job.prompts, job.new_tokens)
        finished = list(zip(job.prompts, served))
        engine.params = None                    # the reference runs alone
        picked = [finished[i] for i in check.pick(
            finished, seed, int(mix.get("check_tokens", 1024)))]
        gaps, margin = check.served_gaps(dm, seed, picked,
                                         geometry["max_len"], margins=True)
        rec = {"workload": args.workload, "seed": seed,
               "tokens": int(gaps.size), "requests": len(picked),
               "malformed": check.malformed(finished, job.new_tokens,
                                            dm.vocab)}
        rec |= _summary("program", gaps)
        if limits:
            rec["program_correct"] = check.correct(
                check.numbers(gaps), limits, rec["malformed"], len(picked))
        if dm.experts:
            # are the wide gaps where the reference's routing nearly tied?
            wide = gaps > 1.0
            near = margin < NEAR_TIE
            rec |= {"wide_tokens": int(wide.sum()),
                    "wide_near_tie": int((wide & near).sum()),
                    "near_tie_tokens": int(near.sum()),
                    "margin_p50": float(np.median(margin))}
        if seed in controls:
            ctrl = check.control_gaps(dm, seed, picked, geometry["max_len"])
            rec |= _summary("control", ctrl)
            if limits:
                # the control's tokens are the float8 reference's own
                # best, in the vocabulary and as many as the program's
                rec["control_correct"] = check.correct(
                    check.numbers(ctrl), limits, 0, len(picked))
                control_verdicts.append((seed, rec["control_correct"]))
        rec["seconds"] = time.perf_counter() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    print(f"total {time.perf_counter() - T_START:.1f} s", file=sys.stderr)
    if not limits:
        print(f"no limits for {args.workload}", file=sys.stderr)
    elif control_verdicts:
        passed = [sd for sd, ok in control_verdicts if ok]
        print(f"control correct on {len(passed)} of {len(control_verdicts)} "
              f"seeds {passed} under limits {limits}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

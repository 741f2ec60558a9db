#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, and the corpus
study of a configuration. Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds 0 1 2 [--seconds 5]
                             [--study] [--faults one_lane_capped ...]
    python3 bench/control.py --workload <cell> --fit-keys 1 2 3 ...
                             [--fit-faults fit_half_batch ...]

It builds the cell's index once, as a run does, and for each seed drives
the cell's own traffic through the program for ``--seconds`` in that
seed's order of the queries, and prints one JSON line with:

* ``program``: the numbers compared (``harness.check``) for the
  program's answers;
* ``control``: the same numbers for the control, the reference computed
  in bfloat16 (the precision below the configuration's float32) and put
  in the program's place, on the same sampled queries (for a quantizer
  trained by ``fit``, on the same first steps too).

``--faults`` adds one line per fault of ``faults.py`` (``SHARD_FAULTS``
for a sharded cell alone) and per seed of the first three: the numbers
compared for the program with that fault planted under the timed path,
at the cell's own size. ``--study`` adds, on the first line, the
corpus's local intrinsic dimension (Levina-Bickel MLE at k=20 over 2000
pool queries) and the recall@10 at each h of ``H_GRID`` (program path,
4096 pool queries).

``--fit-keys`` reads the fit numbers alone, for a configuration trained by
``fit``: from the cell's step 0 it runs ``fit``'s first steps with each key
``fold_in(k_pq, key)`` (a run uses key 1) and stops, and prints one line a
key with the program's and the control's numbers. ``--fit-faults`` adds
one line per fault of ``faults.FIT_FAULTS`` and per key of the first
three.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import faults
import harness

H_GRID = (16, 24, 32, 48, 64, 96, 128, 192, 256)
SERVING_FAULTS = {**faults.FAULTS, **faults.SHARD_FAULTS}


def lid_mle(base, queries, k: int = 20) -> dict:
    """Levina-Bickel MLE of the local intrinsic dimension at ``k``."""
    import reference

    _, d2 = reference.exact_knn(base, queries, k)
    t = np.sqrt(np.maximum(np.asarray(d2, np.float64), 1e-30))
    inv = np.mean(np.log(t[:, -1:] / t[:, :-1]), axis=1)   # (Q,)
    return {"k": k, "points": int(t.shape[0]),
            "mean_of_estimates": float(np.mean(1.0 / inv)),
            "inverse_of_mean_inverse": float(1.0 / np.mean(inv))}


def recall_curve(engine, pool, gt, skw, n: int = 4096) -> list:
    import jax

    out = []
    rows = np.arange(min(n, pool.shape[0]))
    for h in H_GRID:
        kw = dict(skw, h=h)
        jax.block_until_ready(engine.search(pool[rows[:1024]], **kw))
        t0 = time.perf_counter()
        res = [engine.search(pool[rows[s:s + 1024]], **kw)
               for s in range(0, len(rows), 1024)]
        ids = np.concatenate([np.asarray(r.ids) for r in res])
        dt = time.perf_counter() - t0
        rec = harness.recall([harness.SimpleNamespace(rows=rows, ids=ids)],
                             gt, 10)
        out.append({"h": h, "recall_at_10": rec,
                    "mean_rounds": float(np.mean(np.concatenate(
                        [np.asarray(r.rounds) for r in res]))),
                    "mean_n_dist": float(np.mean(np.concatenate(
                        [np.asarray(r.n_dist) for r in res]))),
                    "host_s_per_1024": dt / (len(rows) / 1024)})
        harness.log(f"h={h}: {out[-1]}")
    return out


def readings(args, seeds, *, require_chip: bool = True,
             overrides: dict | None = None):
    """Build the cell's index once (it comes from the configuration's
    ``data_seed``, as in every run), then yield one reading per seed."""
    import jax

    cell, _, conf, traffic, _, _ = harness.find_cell(args.workload,
                                                     overrides)
    if require_chip:
        harness.check_chip(cell["chips"])
    phases = {}
    engine, index, pool, gt = harness.build(conf, traffic, phases)
    skw = harness.search_kwargs(conf, traffic)
    study = {}
    if args.study:
        study = {"lid": lid_mle(index["base"], pool[:2000]),
                 "recall_h": recall_curve(engine, pool, gt, skw)}
    def drive(seed):
        order = harness.pool_order(seed, pool.shape[0])
        for i in range(2):
            rows = harness.batch_rows(i, traffic["batch"], order)
            jax.block_until_ready(engine.search(pool[rows], **skw))
        records, _, _ = harness.closed_loop(engine, pool, order, traffic,
                                            skw, args.seconds)
        program, _ = harness.check(records, index, pool, conf, traffic,
                                   skw, seed)
        return records, program

    for seed in seeds:
        records, program = drive(seed)
        yield {"workload": args.workload, "seed": seed, "h": skw["h"],
               "setup": phases, **study, "calls": len(records),
               "recall_at_10": harness.recall(records, gt, 10),
               "program": program,
               "control": control_numbers(records, index, pool, conf,
                                          traffic, skw, seed)}
        study = {}
    for name in getattr(args, "faults", None) or []:
        search = type(engine).search
        plant = SERVING_FAULTS[name](search)
        engine.search = lambda queries, **kw: plant(engine, queries, **kw)
        try:
            for seed in seeds[:3]:
                _, program = drive(seed)
                yield {"workload": args.workload, "seed": seed,
                       "fault": name, "program": program}
        finally:
            del engine.search


def control_numbers(records, index, pool, conf, traffic, skw, seed):
    """``harness.check`` with the bfloat16 reference in the program's
    place, on the same sampled calls: its code table, and its answers."""
    import jax.numpy as jnp

    import reference

    spy = index["fit"]
    codes = reference.encode(index["base"], index["r"], index["codebooks"],
                             conf["quantizer"]["layout"], dtype=jnp.bfloat16)
    sharded = "medoids" in index
    if sharded:   # laid out as the served table is
        codes = reference.stack_rows(codes, index["codes"].shape[0])
    index = dict(index, fit=None, codes=codes)
    pick = harness.sample_calls(records, traffic, seed)
    answer = reference.answer_sharded if sharded else reference.answer
    ids, d = answer(
        [pool[records[i].rows] for i in pick], index,
        layout=conf["quantizer"]["layout"], h=skw["h"], k=skw["k"],
        max_steps=skw["max_steps"], dtype=jnp.bfloat16)
    swapped, start = list(records), 0
    for i in pick:
        r, n = records[i], len(records[i].rows)
        swapped[i] = harness.SimpleNamespace(rows=r.rows, ids=ids[start:start + n],
                                             dists=d[start:start + n])
        start += n
    nums, _ = harness.check(swapped, index, pool, conf, traffic, skw,
                            seed)
    if spy is not None:
        nums.update(fit_numbers(spy, index["base"], conf)[1])
    return nums


def fit_numbers(spy, base, conf):
    """The fit numbers of the program's first steps and of the bfloat16
    control in their place."""
    import fit_reference as fr

    program, fit = spy.program(), conf["quantizer"]["fit"]
    ref = fr.steps(program["params0"], base, spy.feeds, fit)
    return (fr.numbers(program, ref),
            fr.control(program["params0"], base, spy.feeds, fit, ref))


def fit_readings(args, *, require_chip: bool = True,
                 overrides: dict | None = None):
    """One line per key of ``args.fit_keys``, then per fault of
    ``args.fit_faults`` and key of the first three."""
    import contextlib

    import jax

    cell, _, conf, traffic, _, _ = harness.find_cell(args.workload,
                                                     overrides)
    if require_chip:
        harness.check_chip(cell["chips"])
    parts = harness.step0(conf, traffic, {})

    def first_steps(key):
        spy = harness.FitSteps(stop=True)
        t0 = time.perf_counter()
        try:
            harness.train(parts, conf["quantizer"],
                          jax.random.fold_in(parts.k_pq, key), spy)
        except harness.StopFit:
            pass
        return spy, time.perf_counter() - t0

    runs = [(key, None) for key in args.fit_keys]
    runs += [(key, name) for name in args.fit_faults
             for key in args.fit_keys[:3]]
    for key, fault in runs:
        with (faults.planted_fit(fault) if fault
              else contextlib.nullcontext()):
            spy, seconds = first_steps(key)
        program, control = fit_numbers(spy, parts.base, conf)
        out = {"workload": args.workload, "fit_key": key, "fault": fault,
               "seconds": seconds, "losses": spy.program()["losses"],
               "program": program}
        if fault is None:
            out["control"] = control
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--study", action="store_true")
    ap.add_argument("--faults", nargs="*", default=[],
                    choices=sorted(SERVING_FAULTS))
    ap.add_argument("--fit-keys", type=int, nargs="+", default=[])
    ap.add_argument("--fit-faults", nargs="*", default=[],
                    choices=sorted(faults.FIT_FAULTS))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.REPO / "src"))
    from repro.launch import compile_cache

    import jax
    try:
        harness.check_chip(1)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    lines = (fit_readings(args) if args.fit_keys
             else readings(args, args.seeds))
    for out in lines:
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

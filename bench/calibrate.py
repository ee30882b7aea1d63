#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --config rgcn-mag --traffic frozen \\
        --seeds 101,102,... --control-seeds 101,102,103 --out FILE

One process, one dataset: for each seed a ``Heta`` session trains its three
set-up steps through ``Heta.fit`` exactly as ``bench/run.py`` does (no
measured window: the readings need none), and the numbers of
``bench/compare.py`` are read against the reference.  For the control
seeds, the control (the reference computed at the precision below the
configuration's, ``reference.CONTROL``) and the reference with each planted
fault of ``reference.FAULTS`` are put in the program's place and read the
same way.
Each reading is printed as one JSON line and all of them are written to
``--out``.  The benchmark's own runs never run this.
"""

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".cache" / "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from bench import dataset, harness, reference
    from repro.launch.compile_cache import enable_compile_cache

    bm = harness.benchmark()
    entry = next(c for c in bm["configs"] if c["name"] == args.config)
    name = f"{args.config}.{args.traffic}"
    bm = {**bm, "configs": [entry], "workloads": [
        {"name": name, "config": args.config, "traffic": args.traffic,
         "chips": 1}]}
    cell = harness.open_cell(name, bm)
    devs = harness.devices_for(1)
    enable_compile_cache()
    ds = dataset.load(cell.config["dataset"])
    matmul = cell.config["precision"]["matmul"]
    setup = reference.make_setup(ds, cell.heta, matmul)
    graph = dataset.to_hetgraph(ds)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in sorted(set(seeds) | control):
        t0 = time.perf_counter()
        with harness.matmul_precision(cell):
            sess = harness.start_session(cell, graph, seed)
            warm = harness.warm_up(sess, setup)
        harness.release(sess)
        del sess
        gc.collect()
        row = {"cell": name, "seed": seed, "losses": warm.losses,
               "walls": warm.walls, "kind": devs[0].device_kind}
        ref = harness.Reference(setup, ds, warm, seed)
        if seed in seeds:
            row["program"] = ref.program()
        if seed in control:
            row["control"] = ref.stand_in(reference.CONTROL[matmul])
            for fault in reference.FAULTS:
                row[fault] = ref.stand_in(fault=fault)
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

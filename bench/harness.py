"""One run of one cell: set-up, the measured window, the check, the result.

The cell, its configuration and its traffic mix are found by name from
``BENCHMARK.json``; the configuration's ``heta`` object merged with the
traffic's is the ``HetaConfig`` of the session, and ``--seed`` is its
``run.seed`` (weights and batch order).  The run:

1. refuses anything but a TPU with the chips the cell asks for;
2. keeps JAX's compile cache in ``bench/.cache/jax`` of the checkout;
3. loads the cell's dataset (``bench/dataset.py``) and drives a ``Heta``
   session through ``build_graph -> partition -> profile_and_cache ->
   compile``;
4. trains three steps with ``Heta.fit`` (they compile, and the check reads
   the optimizer state after the first and the weights after the third);
5. times one ``Heta.fit(steps=k)``, k sized from the third step so that
   the window lasts at least ``--seconds`` (and ``fit`` again for the time
   left where that step ran slower than the window's);
6. with ``--trace 1``, traces two more steps under the profiler;
7. frees the session, runs the reference over the first three steps'
   batches (``bench/reference.py``), compares (``bench/compare.py``), and
   prints the result line.

Per-layer and end-to-end metrics are computed by one reader each,
``bench/metrics/<name>.py``, from what the run collected.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = BENCH / ".cache"
WARM_STEPS = 3  # compiled, and compared with the reference
TRACED_STEPS = 2


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find(root: Path, kind: str, name: str) -> Path:
    """``<root>/<kind>/<name>``, else the same under ``bench/``."""
    path = root / kind / name
    return path if path.exists() else BENCH / kind / name


def reader(metric: str, root: Path = BENCH):
    """The reader of one metric: ``metrics/<metric>.py``'s ``read``."""
    path = _find(root, "metrics", f"{metric}.py")
    return _module(path, "bench_metric_" + metric.replace(".", "_")).read


def _merge(a: dict, b: dict) -> dict:
    out = {k: dict(v) if isinstance(v, dict) else v for k, v in a.items()}
    for k, v in b.items():
        out[k] = {**out.get(k, {}), **v} if isinstance(v, dict) else v
    return out


def open_cell(name: str, bm: Optional[dict] = None,
              root: Path = BENCH) -> SimpleNamespace:
    """A workload of ``BENCHMARK.json`` (or of ``bm``) with its
    configuration, traffic mix and limits; the last two, and the metric
    readers, are files under ``root`` named after the traffic and the cell."""
    bm = bm or benchmark()
    wl = next((w for w in bm["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bm['workloads']]}")
    entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    config = load_json(ROOT / entry["file"])
    traffic = load_json(_find(root, "traffic", f"{wl['traffic']}.json"))
    limits = _find(root, "limits", f"{name}.json")
    return SimpleNamespace(
        name=name, workload=wl, config=config, traffic=traffic,
        heta=_merge(config["heta"], traffic.get("heta", {})),
        limits=load_json(limits)["limits"] if limits.exists() else None,
        bm=bm, root=root)


def metrics_of(cell, kind: str) -> List[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` metrics."""
    return [m for m in cell.bm[kind]
            if "workloads" not in m or cell.name in m["workloads"]]


def devices_for(chips: int):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


class CompileCounter:
    """XLA backend compiles (persistent-cache loads included), counted
    through ``jax.monitoring`` as ``chip_smoke.CompileCounter`` does."""

    def __init__(self):
        import jax

        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


# --------------------------------------------------------------------------
# the session
# --------------------------------------------------------------------------


def matmul_precision(cell):
    """The configuration's matmul precision for everything the program
    traces (``jax.default_matmul_precision`` is part of jit's cache key, so
    every call of the program runs inside it)."""
    import jax

    return jax.default_matmul_precision(cell.config["precision"]["matmul"])


def start_session(cell, graph, seed: int):
    from repro.api import Heta, HetaConfig

    heta = _merge(cell.heta, {"run": {"seed": int(seed)}})
    sess = Heta(HetaConfig.from_dict(heta))
    sess.build_graph(graph=graph)
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    return sess


class BatchTap:
    """Keeps host copies of the batches the first ``n`` steps trained on,
    read where the session hands each batch to its executor's device step
    (``Executor.step_staged``, the executor protocol's seam)."""

    def __init__(self, sess, setup, n: int):
        from bench import reference

        self.batches: List[dict] = []
        inner = sess.executor.step_staged

        def step_staged(s, plan, state, batch, arrays):
            if len(self.batches) < n:
                self.batches.append(reference.host_batch(batch, setup))
            return inner(s, plan, state, batch, arrays)

        sess.executor.step_staged = step_staged


def _stack_leaves(sess, setup, stacks) -> Dict[str, list]:
    """Per leaf of the reference, the program's copies of it: the SPMD
    executor keeps each parameter group in a ``[shard, slot, ...]`` stack
    (``StackedPlan.scope_keys`` names the group in each slot)."""
    import numpy as np

    from bench.reference import model_module

    scope = {name: sc for name, sc, *_ in model_module(setup.model).LEAVES}
    plan = sess.plan.plan
    out: Dict[str, list] = {}
    for layer in plan.layers:
        for leaf, arr in stacks[f"layer{layer}"].items():
            a = np.asarray(arr)
            for p, row in enumerate(plan.scope_keys[(scope[leaf], layer)]):
                for u, key in enumerate(row):
                    out.setdefault(f"{key}/{leaf}", []).append(a[p, u])
    for leaf in ("w", "b"):
        out[f"head/{leaf}"] = [np.asarray(stacks["head"][leaf])]
    return out


def _row_leaves(sess, setup, what: str) -> Dict[str, list]:
    if not setup.train_learnable:
        return {}
    snap = sess.engine.state_snapshot()
    return {f"table/{t}": [snap[what][t]] for t in setup.learnable}


def warm_up(sess, setup) -> SimpleNamespace:
    """The first three ``fit`` steps, with what the check reads of them."""
    import jax

    tap = BatchTap(sess, setup, WARM_STEPS)
    walls, m1, params3 = [], None, None
    for i in range(WARM_STEPS):
        t0 = time.perf_counter()
        sess.fit(steps=1)
        jax.block_until_ready(sess.state)
        walls.append(time.perf_counter() - t0)
        if i == 0:
            m1 = {**_stack_leaves(sess, setup, sess.state["opt"]["m"]),
                  **_row_leaves(sess, setup, "m")}
    params3 = {**_stack_leaves(sess, setup, sess.state["stacks"]),
               **_row_leaves(sess, setup, "tables")}
    return SimpleNamespace(batches=tap.batches, walls=walls,
                           losses=[float(x) for x in sess.losses[:WARM_STEPS]],
                           m1=m1, params3=params3)


def window(sess, seconds: float, step_s: float, batch: int, counter):
    """Training steps through ``Heta.fit(steps=k)`` for at least ``seconds``:
    k from the last set-up step's time; where that step ran slower than the
    window's (a cold host), ``fit`` again for the time left, at the window's
    own rate."""
    import jax
    import numpy as np

    k = max(2, math.ceil(seconds / max(step_s, 1e-3)))
    n0, c0 = len(sess.losses), counter.compiles
    steps = 0
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            sess.fit(steps=k)
            jax.block_until_ready(sess.state)
            steps += k
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
            k = math.ceil((seconds - wall) / (wall / steps))
    losses = np.asarray(sess.losses[n0:], np.float64)
    log("window steps (host + device s): " + " ".join(
        f"{h:.3f}+{d:.3f}" for h, d in zip(sess.host_times[n0:], sess.step_times[n0:])))
    return SimpleNamespace(
        steps=steps, wall_s=wall, batch=batch, compiles=counter.compiles - c0,
        host_s=float(sum(sess.host_times[n0:])),
        failed=int(np.sum(~np.isfinite(losses))))


def trace_steps(sess, out_dir: Path) -> dict:
    """Trace two whole steps with the Python tracer on; reduce the trace."""
    import jax
    from jax.profiler import ProfileOptions

    from bench import tracing

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = ProfileOptions()
    opts.python_tracer_level = 1
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            sess.fit(steps=TRACED_STEPS)
            jax.block_until_ready(sess.state)
    finally:
        jax.profiler.stop_trace()
    own = tracing.functions_of((ROOT / "src" / "repro").rglob("*.py"))
    summary = tracing.reduce(tracing.load(tracing.find_trace(out_dir)),
                             "bench.traced", own)
    shutil.rmtree(out_dir, ignore_errors=True)
    summary["steps"] = TRACED_STEPS
    return summary


def release(sess) -> None:
    sess.close_serving()
    sess.close_pipeline()


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------


class Reference:
    """The reference over one run's set-up batches, and the readings of
    anything put beside it: the program, or a control."""

    def __init__(self, setup, ds, warm, seed: int):
        from bench import reference

        self.setup, self.warm = setup, warm
        self.init = reference.init_params(setup, seed)
        self.tables = {**ds["features"], **reference.init_tables(setup, ds, seed)}
        self.faults = sum(reference.check_batch(hb, setup, ds)
                          for hb in warm.batches)
        self.ref = self.train()
        if setup.train_learnable:
            self.init.update({f"table/{t}": self.tables[t]
                              for t in setup.learnable})

    def train(self, precision: str = None, fault: str = None) -> dict:
        from bench import reference

        return reference.train(self.setup, self.init, self.tables,
                               self.warm.batches, precision, fault)

    def readings(self, prog: dict) -> dict:
        from bench import compare

        return compare.readings(prog, self.ref, self.init, self.faults)

    def program(self) -> dict:
        """The program's readings."""
        w = self.warm
        return self.readings({"losses": w.losses, "m1": w.m1,
                              "params3": w.params3})

    def stand_in(self, precision: str = None, fault: str = None) -> dict:
        """Readings of the reference itself put in the program's place, at
        a lower precision (a control, ``reference.CONTROL``) or with a fault
        planted."""
        from bench.compare import B1

        low = self.train(precision, fault)
        wrap = lambda d: {k: [v] for k, v in d.items()}
        # Adam's m after one step is (1 - b1) times the first gradient
        return self.readings({
            "losses": low["losses"],
            "m1": wrap({k: (1.0 - B1) * g for k, g in low["grads"].items()}),
            "params3": wrap(low["params"])})


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------


def _device(devs, trace: Optional[dict]) -> dict:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs),
           "memory_peak_bytes": max(peaks) if peaks else None}
    if trace is not None:
        out["busy_s"] = trace["busy_s"]
        out["window_s"] = trace["window_s"]
    return out


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        *, require_tpu: bool = True, bm: Optional[dict] = None,
        root: Path = BENCH) -> dict:
    """One run of cell ``name``; returns the result (the last line).
    ``require_tpu=False``, ``bm`` and ``root`` are for the CPU tests."""
    import jax

    from bench import dataset, reference
    from repro.launch.compile_cache import enable_compile_cache

    cell = open_cell(name, bm, root)
    if not cell.limits:
        raise KeyError(f"no limits for cell {name!r} (limits/{name}.json)")
    chips = int(cell.workload["chips"])
    devs = devices_for(chips) if require_tpu else jax.devices()[:chips]
    enable_compile_cache()
    counter = CompileCounter()
    peaks = load_json(BENCH / "peaks.json")
    kind = devs[0].device_kind
    if require_tpu and kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")

    with matmul_precision(cell), jax.profiler.TraceAnnotation("bench.setup"):
        t0 = time.perf_counter()
        ds = dataset.load(cell.config["dataset"])
        setup = reference.make_setup(ds, cell.heta,
                                     cell.config["precision"]["matmul"])
        log(f"dataset {time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        sess = start_session(cell, dataset.to_hetgraph(ds), seed)
        log(f"session stages {time.perf_counter() - t0:.3f} s "
            f"{ {k: round(v, 3) for k, v in sess.stage_times.items()} }")
        warm = warm_up(sess, setup)
        log("set-up steps " + " ".join(f"{w:.3f}" for w in warm.walls)
            + f" s; compiles so far {counter.compiles}")
    setup_s = time.perf_counter() - t_start
    batch = int(cell.heta["data"]["batch_size"])
    with matmul_precision(cell):
        win = window(sess, seconds, warm.walls[-1], batch, counter)
        log(f"set-up {setup_s:.3f} s; window {win.steps} steps in "
            f"{win.wall_s:.3f} s, host {win.host_s:.3f} s, {win.compiles} compiles")
        t0 = time.perf_counter()
        summary = trace_steps(sess, CACHE / "trace" / name) if trace else None
    if trace:
        log(f"traced {TRACED_STEPS} steps and reduced in "
            f"{time.perf_counter() - t0:.3f} s")
    device = _device(devs, summary)
    release(sess)
    del sess
    gc.collect()

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.reference"):
        read = Reference(setup, ds, warm, seed).program()
    log(f"reference and comparison {time.perf_counter() - t0:.3f} s; worst "
        f"leaves: grad_gap {read['grad_gap']['leaf']}, change_gap "
        f"{read['change_gap']['leaf']} ({read['change_gap']['leaves']} of "
        f"{read['change_gap']['of']} leaves move)")
    from bench.compare import judge

    correct = judge(read, cell.limits)
    ctx = SimpleNamespace(cell=cell, setup=setup, batch=batch, chips=chips,
                          peaks=peaks.get(kind), setup_s=setup_s, window=win,
                          trace=summary)
    kind_of = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(cell, kind_of):
        value = reader(m["name"], cell.root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": win.steps,
              "failed": win.failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {k: {"value": read[k]["value"], "limit": read[k]["limit"]}
                        for k in cell.limits}
    return result


def log(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


def print_result(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()

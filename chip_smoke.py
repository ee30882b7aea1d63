#!/usr/bin/env python3
"""Bring-up smoke: Heta's main path, once, at full width, on a TPU.

Run from the root of a checkout:

    python chip_smoke.py               # one chip (the default run)
    python chip_smoke.py --four-chips  # a four-chip host: the cross-chip path

The default run builds an ogbn-mag-shaped graph at scale 1.0 from ``--seed``
(736,389 papers with 128-d features; 1,134,649 authors, 8,740 institutions
and 59,965 fields of study as learnable 64-d tables; ~37M edges) and hands
it to every session.  For each of rgcn, rgat and hgt it goes through a
``Heta`` session — partition -> profile_and_cache -> compile -> fit — on the
``raf_spmd`` executor at hidden 64, 4 heads, batch 1024, fanouts 25,20
(two layers), with the default kernels (the compiled Pallas kernels on a
TPU), and checks the step losses against the ``vanilla`` float32 oracle on
the same seed and batches.  The cache budget holds every feature and
learnable row.  Then, for rgcn on a copy of the graph with in-degree capped
at 20 (exhaustive full-graph inference needs bounded neighborhoods), it
runs ``infer_all`` and answers ``EmbeddingServer`` queries of 1024 papers,
checking the scores against the minibatch forward on exhaustive batches.

``--four-chips`` runs only rgcn on ``raf_spmd`` over a ``1x4`` mesh with 4
partitions — the RAF ``psum`` crosses chips — and the same configuration
folded onto a ``1x1`` mesh, and checks that the losses agree.

Every phase prints its numbers on lines of its own; the last line of
standard output is one JSON object naming the device.  Without a TPU, or
outside a checkout (no ``src/repro`` beside this file), the script exits
non-zero and prints no result.  The persistent compilation cache lives in
``JAX_COMPILATION_CACHE_DIR`` when set, else in ``.jax_cache`` at the root
of the checkout.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

SCALE = 1.0  # ogbn-mag's own node and edge counts
BATCH = 1024
FANOUTS = (25, 20)
HIDDEN, HEADS, LEARNABLE_DIM = 64, 4, 64
PARTITIONS = 4
MODELS = ("rgcn", "rgat", "hgt")
STEPS = 3  # per session; steps 2.. are checked for recompiles
LOSS_TOL = 1e-2  # raf_spmd (default TPU matmul precision) vs the f32 oracle
FOLD_TOL = 1e-3  # 1x4 mesh vs its 1x1 fold: same math, psum order differs
SERVE_DEGREE_CAP = 20  # <= every fanout, so exhaustive batches fit the plan
SERVE_QUERIES = 4
# serving scores vs the minibatch forward: both run f32 matmuls at the TPU's
# default (bfloat16-pass) precision, through different reduction orders;
# allow 8 bfloat16 epsilons of the logit scale
SERVE_TOL = 8 * 2.0 ** -8


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache loads included) and
    persistent-cache hits through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _config(model: str, executor: str, *, mesh=(1, 1), steps: int, seed: int,
            cache_mb: int):
    from repro.api import HetaConfig

    return HetaConfig.from_dict(dict(
        data=dict(dataset="ogbn-mag", scale=SCALE, fanouts=FANOUTS,
                  batch_size=BATCH),
        partition=dict(num_partitions=PARTITIONS),
        model=dict(model=model, hidden=HIDDEN, num_heads=HEADS,
                   learnable_dim=LEARNABLE_DIM),
        cache=dict(cache_mb=cache_mb, measured_penalties=True),
        run=dict(executor=executor, mesh_shape=mesh, steps=steps, seed=seed),
    ))


def _full_cache_mb(graph) -> int:
    """A cache budget that holds every row: feature rows, and learnable
    rows with their two Adam states (``embed.profiler.row_bytes``)."""
    from repro.embed.profiler import row_bytes

    total = sum(
        n * row_bytes(graph.feat_dim(t) or LEARNABLE_DIM,
                      t not in graph.features)
        for t, n in graph.num_nodes.items())
    return -(-total // 2**20) + 1


def _peak_gib(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def _train(cfg, graph, counter, tag: str):
    """One session through partition -> profile_and_cache -> compile -> fit,
    one ``fit(steps=1)`` at a time so each step is timed to completion
    (``cfg.run.steps >= 2``: steps after the first are checked for
    recompiles)."""
    import jax
    import numpy as np

    from repro.api import Heta

    sess = Heta(cfg)
    t0 = time.perf_counter()
    sess.build_graph(graph=graph)
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    setup_s = time.perf_counter() - t0

    # compile the train step ahead of the first step: the program fit() runs
    kernels = None
    t0 = time.perf_counter()
    if cfg.run.executor == "raf_spmd":
        probe = sess.sampler.sample_batch(graph.train_nodes[:BATCH])
        arrays = sess.executor.stage(sess, sess.plan, probe)
        compiled = sess.plan.step.lower(
            sess.state["stacks"], sess.state["opt"], arrays).compile()
        kernels = compiled.as_text().count("tpu_custom_call")
        del probe, arrays, compiled  # ~1.6 GB of staged features
    compile_s = time.perf_counter() - t0

    walls = []
    for i in range(cfg.run.steps):
        if i == 1:
            first_compiles = counter.compiles
        t0 = time.perf_counter()
        sess.fit(steps=1)
        jax.block_until_ready(sess.state)
        walls.append(time.perf_counter() - t0)
    later_compiles = counter.compiles - first_compiles

    losses = [float(x) for x in sess.losses]
    pen = sess.engine.penalties
    print(f"[{tag}] setup {setup_s:.3f} s  step-compile {compile_s:.3f} s  "
          f"first step {walls[0]:.3f} s  later steps "
          + " ".join(f"{w:.3f}" for w in walls[1:])
          + f" s (median {float(np.median(walls[1:])):.3f} s)")
    print(f"[{tag}] losses " + " ".join(f"{x:.6f}" for x in losses))
    if kernels is not None:
        print(f"[{tag}] tpu_custom_call in compiled step: {kernels}")
    print(f"[{tag}] compiles during steps 2..{len(walls)}: {later_compiles}")
    print(f"[{tag}] miss penalties (us/KB): " + "  ".join(
        f"{t}={pen.ratios[t] * 1e6 * 1024:.4f}" for t in sorted(pen.ratios)))
    print(f"[{tag}] peak_bytes_in_use so far: "
          f"{_peak_gib(jax.devices()[0])}")
    _check(all(np.isfinite(losses)), f"{tag}: non-finite loss {losses}")
    return sess, losses, kernels


def _release(sess) -> None:
    """Stop what a session started; the caller then drops its reference and
    collects, so the session's device buffers are freed before the next."""
    sess.close_serving()
    sess.close_pipeline()


def run_one_chip(args, counter) -> None:
    import numpy as np

    from repro.graph.synthetic import ogbn_mag_like

    t0 = time.perf_counter()
    graph = ogbn_mag_like(scale=SCALE, seed=args.seed)
    cache_mb = _full_cache_mb(graph)
    print(f"[graph] {graph.name}: {graph.total_nodes:,} nodes, "
          f"{graph.total_edges:,} edges, built in "
          f"{time.perf_counter() - t0:.3f} s; cache budget {cache_mb} MiB "
          f"(every row)")

    for model in MODELS:
        sess, losses, kernels = _train(
            _config(model, "raf_spmd", steps=STEPS, seed=args.seed,
                    cache_mb=cache_mb), graph, counter, f"{model}/raf_spmd")
        _check(kernels > 0,
               f"{model}: the compiled step contains no Pallas kernel")
        _release(sess)
        del sess
        gc.collect()
        ref_sess, ref, _ = _train(
            _config(model, "vanilla", steps=STEPS, seed=args.seed,
                    cache_mb=cache_mb), graph, counter, f"{model}/vanilla")
        _release(ref_sess)
        del ref_sess
        gc.collect()
        diff = float(np.max(np.abs(np.asarray(losses) - np.asarray(ref))))
        print(f"[{model}] largest |loss(raf_spmd) - loss(vanilla)| = "
              f"{diff:.3e} (limit {LOSS_TOL:g})")
        _check(diff <= LOSS_TOL,
               f"{model}: raf_spmd losses {losses} differ from vanilla {ref} "
               f"by {diff:.3e} > {LOSS_TOL:g}")

    _serve(args, graph, cache_mb, counter)


def _serve(args, graph, cache_mb, counter) -> None:
    """rgcn: fit, ``infer_all``, then ``EmbeddingServer`` queries checked
    against the minibatch forward on exhaustive batches."""
    import numpy as np

    from repro.serve import bounded_graph
    from repro.serve.full_graph import exhaustive_batch, spmd_logits_for_batch

    bg = bounded_graph(graph, SERVE_DEGREE_CAP)
    sess, _, _ = _train(
        _config("rgcn", "raf_spmd", steps=2, seed=args.seed,
                cache_mb=cache_mb), bg, counter, "serve/rgcn")
    t0 = time.perf_counter()
    store = sess.infer_all()
    infer_s = time.perf_counter() - t0
    print(f"[serve] infer_all: {sum(a.shape[0] for a in store.embeddings.values()):,} "
          f"embeddings of {len(store.embeddings)} types in {infer_s:.3f} s")
    server = sess.serve()
    rng = np.random.default_rng(args.seed)
    n_papers = bg.num_nodes[bg.target_type]
    tables = sess.engine.tables_snapshot()
    worst = 0.0
    for q in range(SERVE_QUERIES):
        seeds = rng.choice(n_papers, BATCH, replace=False)
        t0 = time.perf_counter()
        res = server.query(seeds)
        wall_ms = (time.perf_counter() - t0) * 1e3
        _check(res.scores is not None
               and res.scores.shape == (BATCH, bg.num_classes)
               and bool(np.all(np.isfinite(res.scores))),
               f"serve query {q}: bad scores")
        ref = spmd_logits_for_batch(
            sess.plan.plan, sess.state["stacks"],
            exhaustive_batch(bg, sess.spec, seeds), tables,
            kernels=sess.config.kernels)
        scale = max(1.0, float(np.max(np.abs(ref))))
        err = float(np.max(np.abs(res.scores - ref))) / scale
        worst = max(worst, err)
        print(f"[serve] query {q}: {BATCH} seeds in {wall_ms:.3f} ms "
              f"(server latency {res.latency_ms:.3f} ms); "
              f"max |score - minibatch| / scale = {err:.3e}")
    print(f"[serve] largest relative score error {worst:.3e} "
          f"(limit {SERVE_TOL:.3e}); {server.stats().render().strip()}")
    _check(worst <= SERVE_TOL,
           f"serve: scores differ from the minibatch forward by {worst:.3e}")
    _release(sess)


def run_four_chips(args, counter) -> None:
    """rgcn over a 1x4 mesh (partitions on four chips, the RAF psum crosses
    them) against the same configuration folded onto one chip."""
    import jax
    import numpy as np

    from repro.graph.synthetic import ogbn_mag_like

    graph = ogbn_mag_like(scale=SCALE, seed=args.seed)
    cache_mb = _full_cache_mb(graph)
    sess, wide, _ = _train(
        _config("rgcn", "raf_spmd", mesh=(1, 4), steps=STEPS,
                seed=args.seed, cache_mb=cache_mb), graph, counter,
        "rgcn/mesh1x4")
    probe = sess.sampler.sample_batch(graph.train_nodes[:BATCH])
    arrays = sess.executor.stage(sess, sess.plan, probe)
    spans = {k: len(v.sharding.device_set) for k, v in arrays.items()}
    print(f"[mesh1x4] devices per staged array: {spans}")
    _check(all(n == 4 for n in spans.values()),
           f"staged arrays are not placed on 4 chips: {spans}")
    text = sess.plan.step.lower(
        sess.state["stacks"], sess.state["opt"], arrays).compile().as_text()
    n_ar = text.count("all-reduce")
    print(f"[mesh1x4] all-reduce ops in compiled step: {n_ar}")
    _check(n_ar > 0, "the 1x4 step has no cross-chip all-reduce")
    _release(sess)
    del sess, arrays
    gc.collect()
    fold, folded, _ = _train(
        _config("rgcn", "raf_spmd", mesh=(1, 1), steps=STEPS,
                seed=args.seed, cache_mb=cache_mb), graph, counter,
        "rgcn/mesh1x1")
    _release(fold)
    diff = float(np.max(np.abs(np.asarray(wide) - np.asarray(folded))))
    print(f"[four-chips] largest |loss(1x4) - loss(1x1)| = {diff:.3e} "
          f"(limit {FOLD_TOL:g}) on {len(jax.devices())} devices")
    _check(diff <= FOLD_TOL,
           f"1x4 losses {wide} differ from the 1x1 fold {folded} by {diff:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only rgcn on a 1x4 mesh vs its 1x1 fold")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, the weights and the batches")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package at {SRC}; run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    # keep libtpu's own log files out of /tmp: its errors reach stderr
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no backend: {e}", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        (run_four_chips if args.four_chips else run_one_chip)(args, counter)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[total] {time.perf_counter() - t0:.3f} s; {counter.compiles} "
          f"compiles, {counter.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

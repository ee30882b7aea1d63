"""Serving drivers — the HGNN online-inference tier and the LM workbench.

Two tiers share this entry point:

  * **HGNN tier** (default; ``repro.serve``, DESIGN.md §10): train a
    quickstart-sized session, materialize every node's embedding via
    layer-wise full-graph inference (``Heta.infer_all``), start the
    micro-batching ``EmbeddingServer`` (``Heta.serve``) and drive it with
    concurrent lookup threads — printing p50/p99 latency, QPS and per-type
    cache hit rates.  All ``HetaConfig`` flags apply (``--serve-max-batch``,
    ``--serve-max-wait-ms``, ``--steps``, ``--scale``, ...).

  * **LM workbench** (``--arch NAME``): batched prefill + token-by-token
    decode for an assigned transformer architecture.  Production configs
    are exercised via the 512-device dry-run (``repro.launch.dryrun``); on
    a development host ``--reduced`` (the default) runs a shrunken config
    end-to-end with real tensors.

Usage:
  python -m repro.launch.serve --steps 5 --requests 256 --concurrency 8
  python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --batch 4 --prompt-len 64 --new-tokens 32 [--window 16]
"""

from __future__ import annotations

import argparse
import threading
import time

import numpy as np


def _parser() -> argparse.ArgumentParser:
    from repro.api import add_config_args

    ap = argparse.ArgumentParser(
        description="Serving drivers: HGNN online-inference tier (default) "
                    "or the LM decode workbench (--arch).",
    )
    hg = ap.add_argument_group(
        "HGNN tier (default)",
        "layer-wise full-graph inference + micro-batching embedding server; "
        "HetaConfig flags below also apply",
    )
    hg.add_argument("--requests", type=int, default=256,
                    help="lookup requests to fire at the server (default: 256)")
    hg.add_argument("--concurrency", type=int, default=8,
                    help="concurrent client threads (default: 8)")
    hg.add_argument("--ids-per-request", type=int, default=4,
                    help="node ids per lookup (default: 4)")
    hg.add_argument("--max-degree", type=int, default=16,
                    help="cap the synthetic graph's in-degree so full-graph "
                         "inference stays laptop-sized (0 = uncapped)")
    lm = ap.add_argument_group("LM workbench (--arch)")
    lm.add_argument("--arch", default=None,
                    help="run the LM decode workbench for this architecture "
                         "instead of the HGNN tier (e.g. qwen2-1.5b)")
    lm.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="LM workbench only: run the reduced config "
                         "(--no-reduced for full size)")
    lm.add_argument("--batch", type=int, default=4,
                    help="LM workbench only: decode batch size")
    lm.add_argument("--prompt-len", type=int, default=64,
                    help="LM workbench only: prefill prompt length")
    lm.add_argument("--new-tokens", type=int, default=32,
                    help="LM workbench only: tokens to decode")
    lm.add_argument("--window", type=int, default=0,
                    help="LM workbench only: sliding-window size "
                         "(0 = full attention)")
    add_config_args(ap)  # HetaConfig flags (shared --seed, --steps, ...)
    return ap


# --------------------------------------------------------------------------
# HGNN tier
# --------------------------------------------------------------------------


def _serve_hgnn(args) -> None:
    from repro.api import (
        DataConfig, Heta, HetaConfig, ModelConfig, RunConfig,
        config_from_args,
    )
    from repro.serve import bounded_graph

    base = HetaConfig(
        data=DataConfig(dataset="ogbn-mag", scale=0.002, fanouts=(4, 4),
                        batch_size=16),
        model=ModelConfig(hidden=32, num_heads=2, learnable_dim=16),
        run=RunConfig(executor="raf_spmd", steps=5),
    )
    cfg = config_from_args(args, base)
    sess = Heta(cfg)
    g = sess.build_graph()
    if args.max_degree:
        g = bounded_graph(g, args.max_degree)
        sess.build_graph(g)
    print(f"graph: {g.name}  nodes={g.total_nodes:,}  edges={g.total_edges:,}")
    sess.partition()
    sess.profile_and_cache()
    sess.compile()
    sess.fit()
    print(f"trained {cfg.run.steps} steps "
          f"(loss {sess.losses[-1]:.4f})" if sess.losses else "no training")

    t0 = time.perf_counter()
    store = sess.infer_all()
    print(f"infer_all: {sum(a.shape[0] for a in store.embeddings.values()):,} "
          f"embeddings across {len(store.embeddings)} types "
          f"({store.nbytes / 2**20:.1f} MiB"
          f"{', shm-backed' if store.handle else ''}) "
          f"in {time.perf_counter() - t0:.2f} s")

    server = sess.serve()
    n_target = g.num_nodes[g.target_type]

    def client(k: int) -> None:
        rng = np.random.default_rng(cfg.run.seed + k)
        for _ in range(args.requests // args.concurrency):
            nids = rng.integers(0, n_target, args.ids_per_request)
            server.query(nids)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(args.concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = server.stats()
    print(f"served {stats.count} requests in {wall:.2f} s "
          f"({args.concurrency} clients, flush policy: "
          f"max_batch={cfg.serve.max_batch}, "
          f"max_wait_ms={cfg.serve.max_wait_ms})")
    print(stats.render())

    ev = sess.evaluate(num_batches=2, use_full_graph=True)
    print(f"full-graph eval loss: {ev['loss']:.4f}")
    sess.close_serving()


# --------------------------------------------------------------------------
# LM workbench
# --------------------------------------------------------------------------


def _serve_lm(args) -> None:
    import jax
    import jax.numpy as jnp

    import repro.configs.all_archs  # noqa: F401
    from repro.configs.base import ARCHS
    from repro.models import init_decode_cache, init_params, make_prefill_step, make_serve_step

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = cfg.reduced()
    if not cfg.is_decoder:
        raise SystemExit(f"{args.arch} is encoder-only (no decode step)")

    seed = args.seed if args.seed is not None else 0
    rng = np.random.default_rng(seed)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    B, S, N = args.batch, args.prompt_len, args.new_tokens
    window = args.window or None

    prompts = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))
    t0 = time.time()
    if window:
        # window mode: ring-buffer cache; feed the prompt token-by-token
        cache = init_decode_cache(cfg, B, window)
        serve = make_serve_step(cfg, window=window, donate=False)
        logits = None
        for pos in range(S):
            logits, cache = serve(params, cache, prompts[:, pos:pos + 1],
                                  jnp.asarray(pos, jnp.int32))
    else:
        prefill = make_prefill_step(cfg)
        logits, cache = prefill(params, {"tokens": prompts})
        pad = [(0, 0)] * 6
        pad[3] = (0, N)
        if "k" in cache:
            cache["k"] = jnp.pad(cache["k"], pad)
            cache["v"] = jnp.pad(cache["v"], pad)
        serve = make_serve_step(cfg, donate=False)
    jax.block_until_ready(logits)
    print(f"prefill {B}x{S}: {(time.time()-t0)*1e3:.0f} ms")

    token = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    t0 = time.time()
    for pos in range(S, S + N):
        logits, cache = serve(params, cache, token, jnp.asarray(pos, jnp.int32))
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    jax.block_until_ready(token)
    dt = time.time() - t0
    print(f"decode {N} tokens: {dt*1e3:.0f} ms ({dt/N*1e3:.1f} ms/token, "
          f"window={window})")


def main():
    from repro.launch.compile_cache import enable_compile_cache

    args = _parser().parse_args()
    enable_compile_cache()
    if args.arch:
        _serve_lm(args)
    else:
        _serve_hgnn(args)


if __name__ == "__main__":
    main()

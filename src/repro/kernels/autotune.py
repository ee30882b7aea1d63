"""Block-size autotuner for the stacked kernel family (DESIGN.md §8).

The stacked ops historically ran every shape with the hardcoded
``DEFAULT_BLOCKS`` (128, 128, 512).  This pass sweeps clamped block
candidates per (op, shape-class, dtype) under the same VMEM budget
formulas the dispatch uses, and caches the winners in a committed JSON
tuning table (``repro/kernels/tuning_table.json``) that
``repro.kernels.ops.resolve_blocks`` consults when ``KernelConfig.autotune``
is on.

Two scoring modes:

  * ``mode="measured"`` — time the real op (compiled Pallas on TPU; the
    interpret-mode emulation elsewhere, useful only for relative grid-step
    overhead).  The real-TPU sweep is the production path; see ROADMAP.
  * ``mode="analytic"`` — a deterministic cost model (grid-step overhead +
    DMA bytes + MXU flops, all pure arithmetic of the shape and blocks).
    This is the **offline mode for CI**: repeat runs produce bit-identical
    tables, so the committed table can be validated and regenerated
    reproducibly on any host.

Table schema (version 1)::

    {"version": 1, "mode": "analytic", "backend": "cpu",
     "budget_bytes": 16777216,
     "entries": {"<op>/<dtype>/n<2^k>/f<f>/di<d>/do<d>":
                 {"block_n": int, "block_out": int, "block_in": int,
                  "source": "analytic" | "measured", "cost_us": float}}}

Regenerate with ``python -m repro.kernels.autotune --out
src/repro/kernels/tuning_table.json``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.kernels.ops import (
    DEFAULT_BLOCKS,
    TUNING_TABLE_PATH,
    VMEM_BUDGET_BYTES,
    clamp_block,
    load_tuning_table,
    shape_class,
)
from repro.kernels.stacked_relation_agg.ops import (
    stacked_attn_epilogue_vmem_bytes,
    stacked_mean_linear_vmem_bytes,
    stacked_softmax_combine_vmem_bytes,
)

__all__ = [
    "OPS",
    "candidates",
    "analytic_cost_us",
    "measured_cost_us",
    "autotune_op",
    "build_table",
    "save_table",
    "validate_table",
    "DEFAULT_SHAPES",
]

OPS = ("stacked_mean_linear", "stacked_attn_epilogue",
       "stacked_softmax_combine")

# candidate block edges; every tuple is clamped to the shape then deduped
CANDIDATE_BN = (32, 64, 128, 256, 512)
CANDIDATE_BO = (64, 128, 256)
CANDIDATE_BC = (128, 256, 512, 1024)

# deterministic cost-model constants (loosely TPU-shaped; only the *relative*
# ordering of candidates matters, and monotonicity in steps/bytes)
_STEP_US = 1.5  # per-grid-step fixed overhead (DMA setup, loop bookkeeping)
_BYTES_PER_US = 400e3  # ~400 GB/s effective HBM streaming
_FLOPS_PER_US = 100e6  # ~100 TFLOP/s effective MXU fp32


def _vmem_bytes(op: str, n: int, f: int, d_in: int, d_out: int,
                bn: int, bo: int, bc: int) -> int:
    if op == "stacked_mean_linear":
        return stacked_mean_linear_vmem_bytes(
            n, f, d_in, d_out, block_n=bn, block_out=bo, block_in=bc)
    if op == "stacked_attn_epilogue":
        nh, dh = _heads_of(d_out)
        return stacked_attn_epilogue_vmem_bytes(
            n, f, d_in, nh, dh, block_n=bn, block_in=bc, shared_v=False)
    if op == "stacked_softmax_combine":
        nh, dh = _heads_of(d_out)
        return stacked_softmax_combine_vmem_bytes(n, f, nh, dh, block_n=bn)
    raise ValueError(f"unknown autotune op {op!r}; ops: {OPS}")


def _heads_of(d_out: int, head_dim: int = 16) -> Tuple[int, int]:
    """Head split used by the cost/VMEM models — the epilogue working set
    depends only on the product nh*dh, so any consistent split works."""
    dh = min(head_dim, d_out)
    return max(1, d_out // dh), dh


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def candidates(op: str, n: int, f: int, d_in: int,
               d_out: int) -> List[Tuple[int, int, int]]:
    """Clamped, deduped (bn, bo, bc) candidates under the VMEM budget.

    Axes an op does not block over stay at their defaults, so the sweep
    space is the op's real knob set (mean_linear: all three; the fused
    epilogue: bn/bc; softmax_combine: bn only)."""
    bn0, bo0, bc0 = DEFAULT_BLOCKS
    bns: Iterable[int] = CANDIDATE_BN
    bos: Iterable[int] = CANDIDATE_BO if op == "stacked_mean_linear" else (bo0,)
    bcs: Iterable[int] = (
        CANDIDATE_BC if op in ("stacked_mean_linear", "stacked_attn_epilogue")
        else (bc0,)
    )
    seen, out = set(), []
    for bn, bo, bc in itertools.product(bns, bos, bcs):
        key = (clamp_block(bn, n), clamp_block(bo, d_out), clamp_block(bc, d_in))
        if key in seen:
            continue
        seen.add(key)
        if _vmem_bytes(op, n, f, d_in, d_out, *key) <= VMEM_BUDGET_BYTES:
            out.append(key)
    return sorted(out)


def analytic_cost_us(op: str, n: int, f: int, d_in: int, d_out: int,
                     bn: int, bo: int, bc: int,
                     bytes_per_elem: int = 4) -> float:
    """Deterministic per-call cost model: grid-step overhead + streamed
    bytes + MXU flops (pure arithmetic — CI's offline mode).  ``rb`` scales
    every term identically, so it cancels out of the candidate ordering and
    the model uses one slot."""
    if op == "stacked_mean_linear":
        steps = _cdiv(n, bn) * _cdiv(d_out, bo) * _cdiv(d_in, bc)
        step_bytes = (bn * f * bc + bn * f + bc * bo + bo + bn * bo) \
            * bytes_per_elem
        flops = 2 * n * f * d_in + 2 * n * d_in * d_out
    elif op == "stacked_attn_epilogue":
        steps = _cdiv(n, bn) * _cdiv(d_in, bc)
        H = d_out
        step_bytes = (bn * f * bc + bn * f + bn * H + 2 * bc * H + bn * H) \
            * bytes_per_elem
        flops = 2 * 2 * n * f * d_in * H + 4 * n * f * H
    elif op == "stacked_softmax_combine":
        nh, dh = _heads_of(d_out)
        steps = _cdiv(n, bn)
        step_bytes = (bn * f * nh + bn * f + bn * f * d_out + bn * d_out) \
            * bytes_per_elem
        flops = 6 * n * f * d_out
    else:
        raise ValueError(f"unknown autotune op {op!r}; ops: {OPS}")
    return steps * _STEP_US + steps * step_bytes / _BYTES_PER_US \
        + flops / _FLOPS_PER_US


def measured_cost_us(op: str, n: int, f: int, d_in: int, d_out: int,
                     bn: int, bo: int, bc: int, rb: int = 4,
                     repeats: int = 3, interpret: Optional[bool] = None) -> float:
    """Median wall time of the real op at the candidate blocks.

    On TPU this times the compiled kernel (``interpret=None`` auto-selects);
    elsewhere it times the interpret-mode emulation — meaningful only for
    relative grid-step overhead, which is why the committed table ships the
    analytic mode and the TPU sweep is a ROADMAP follow-on."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.stacked_relation_agg.kernel import (
        stacked_attn_epilogue_pallas,
        stacked_mean_linear_pallas,
        stacked_softmax_combine_pallas,
    )
    from repro.kernels.ops import pad_axes, pad_to

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    r = np.random.default_rng(0)
    U = max(2, rb // 2)
    u = jnp.asarray(r.integers(0, U, rb), jnp.int32)
    mask = jnp.asarray(r.random((rb, n, f)) > 0.3)
    if op == "stacked_mean_linear":
        h = jnp.asarray(r.standard_normal((rb, n, f, d_in)), jnp.float32)
        w = jnp.asarray(r.standard_normal((U, d_in, d_out)), jnp.float32)
        b = jnp.zeros((U, 1, d_out), jnp.float32)
        hp = pad_axes(h, {1: bn, 3: bc})
        wp = pad_axes(w, {1: bc, 2: bo})

        def call():
            return stacked_mean_linear_pallas(
                hp, pad_to(mask, 1, bn), wp, pad_to(b, 2, bo), u,
                block_n=bn, block_out=bo, block_in=bc, interpret=interpret)
    elif op == "stacked_attn_epilogue":
        nh, dh = _heads_of(d_out)
        H = nh * dh
        h = jnp.asarray(r.standard_normal((rb, n, f, d_in)), jnp.float32)
        we = jnp.asarray(r.standard_normal((U, d_in, H)) * 0.1, jnp.float32)
        qv = jnp.asarray(r.standard_normal((rb, n, H)), jnp.float32)
        us = jnp.stack([u, u, u])
        hp = pad_axes(h, {1: bn, 3: bc})

        def call():
            return stacked_attn_epilogue_pallas(
                hp, pad_to(mask, 1, bn), pad_to(qv, 1, bn), None,
                pad_to(we, 1, bc), None, None, None, us,
                num_heads=nh, head_dim=dh, block_n=bn, block_in=bc,
                interpret=interpret)
    elif op == "stacked_softmax_combine":
        nh, dh = _heads_of(d_out)
        e = jnp.asarray(r.standard_normal((rb, n, f, nh * dh)), jnp.float32)
        v = jnp.asarray(r.standard_normal((rb, n, f, nh * dh)), jnp.float32)

        def call():
            return stacked_softmax_combine_pallas(
                pad_to(e, 1, bn), pad_to(mask, 1, bn), pad_to(v, 1, bn),
                block_n=bn, interpret=interpret)
    else:
        raise ValueError(f"unknown autotune op {op!r}; ops: {OPS}")

    jax.block_until_ready(call())  # compile outside the timed region
    best = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        best.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(best))


def autotune_op(op: str, n: int, f: int, d_in: int, d_out: int,
                dtype: str = "float32", mode: str = "analytic",
                **measure_kw) -> Tuple[str, Dict]:
    """Sweep one shape class; returns ``(key, winning entry)``."""
    if mode not in ("analytic", "measured"):
        raise ValueError(f"mode must be analytic|measured, got {mode!r}")
    cost = analytic_cost_us if mode == "analytic" else (
        lambda *a: measured_cost_us(*a, **measure_kw))
    best, best_cost = None, float("inf")
    for bn, bo, bc in candidates(op, n, f, d_in, d_out):
        c = float(cost(op, n, f, d_in, d_out, bn, bo, bc))
        # strict < with sorted candidates: ties break toward smaller blocks,
        # deterministically
        if c < best_cost:
            best, best_cost = (bn, bo, bc), c
    assert best is not None, "no candidate fit the VMEM budget"
    key = shape_class(op, n, f, d_in, d_out, dtype)
    return key, {
        "block_n": best[0], "block_out": best[1], "block_in": best[2],
        "source": mode, "cost_us": round(best_cost, 3),
    }


# mag-shaped workload classes (mirrors benchmarks/kernels_bench.py) plus the
# paper-scale widths the VMEM tests pin down
DEFAULT_SHAPES: Tuple[Tuple[str, int, int, int, int], ...] = (
    ("stacked_mean_linear", 1024, 25, 128, 64),    # mag_l1
    ("stacked_mean_linear", 2048, 20, 64, 64),     # mag_l2_shared
    ("stacked_mean_linear", 4096, 25, 789, 349),   # donor-wide features
    ("stacked_mean_linear", 25600, 25, 1024, 64),  # IGB-HET-scale
    ("stacked_attn_epilogue", 1024, 25, 128, 64),  # mag rgat/hgt l1
    ("stacked_attn_epilogue", 2048, 20, 64, 64),   # mag l2
    ("stacked_attn_epilogue", 25600, 25, 1024, 64),
    ("stacked_softmax_combine", 1024, 25, 4, 64),
    ("stacked_softmax_combine", 2048, 20, 4, 64),
)


def build_table(shapes=DEFAULT_SHAPES, mode: str = "analytic",
                **measure_kw) -> Dict:
    import jax

    entries = {}
    for op, n, f, d_in, d_out in shapes:
        key, entry = autotune_op(op, n, f, d_in, d_out, mode=mode,
                                 **measure_kw)
        entries[key] = entry
    return {
        "version": 1,
        "mode": mode,
        "backend": jax.default_backend() if mode == "measured" else "any",
        "budget_bytes": VMEM_BUDGET_BYTES,
        "entries": dict(sorted(entries.items())),
    }


def save_table(table: Dict, path=None) -> Path:
    p = Path(path) if path else TUNING_TABLE_PATH
    with open(p, "w") as fh:
        json.dump(table, fh, indent=1, sort_keys=True)
        fh.write("\n")
    load_tuning_table.cache_clear()  # dispatch re-reads the new winners
    return p


def validate_table(table: Dict) -> None:
    """Schema check for the committed table (CI gate)."""
    if table.get("version") != 1:
        raise ValueError(f"bad tuning-table version: {table.get('version')!r}")
    entries = table.get("entries")
    if not isinstance(entries, dict):
        raise ValueError("tuning table has no 'entries' dict")
    for key, e in entries.items():
        try:
            op, _, nb, fb, dib, dob = key.split("/")
            n, f = int(nb[1:]), int(fb[1:])
            d_in, d_out = int(dib[2:]), int(dob[2:])
        except ValueError:
            raise ValueError(f"malformed tuning-table key {key!r}") from None
        if op not in OPS:
            raise ValueError(f"entry {key!r}: unknown op {op!r}")
        for field in ("block_n", "block_out", "block_in"):
            v = e.get(field)
            if not isinstance(v, int) or v <= 0:
                raise ValueError(f"entry {key!r}: {field} must be a positive "
                                 f"int, got {v!r}")
        if e.get("source") not in ("analytic", "measured"):
            raise ValueError(f"entry {key!r}: bad source {e.get('source')!r}")
        # winners must respect the same VMEM budget the dispatch enforces
        vb = _vmem_bytes(op, n, f, d_in, d_out,
                         e["block_n"], e["block_out"], e["block_in"])
        budget = table.get("budget_bytes", VMEM_BUDGET_BYTES)
        if vb > budget:
            raise ValueError(
                f"entry {key!r}: blocks need {vb} B of VMEM, over the "
                f"{budget} B budget")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(TUNING_TABLE_PATH),
                    help="tuning-table path to write")
    ap.add_argument("--mode", choices=("analytic", "measured"),
                    default="analytic")
    args = ap.parse_args(argv)
    table = build_table(mode=args.mode)
    p = save_table(table, args.out)
    print(f"wrote {len(table['entries'])} entries -> {p}")


if __name__ == "__main__":
    main()

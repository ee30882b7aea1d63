"""Shared benchmark utilities: timers, CSV rows, the machine-readable
record sink behind ``BENCH_kernels.json``, and the α-β cost model used to
project communication volumes to the paper's testbed wall-clock."""

from __future__ import annotations

import json
import time
from typing import Callable, Dict, List

ROWS: List[str] = []
RECORDS: List[Dict] = []


def _host_fields() -> Dict:
    """Backend + core count stamped on every record: timing rows are only
    comparable against rows measured on the same substrate."""
    import os

    try:
        import jax

        backend = jax.default_backend()
    except Exception:  # pragma: no cover - jax always importable in-repo
        backend = "unknown"
    return {"backend": backend, "cpus": os.cpu_count()}


def emit(name: str, us_per_call: float, derived: str = "", **record) -> None:
    """Print + collect one benchmark row.

    Keyword fields (``shape=``, ``gflops=``, ``vmem_bytes=``, ...) make the
    row machine-readable: it lands in :data:`RECORDS` and is written out by
    :func:`write_records` — the repo's perf trajectory
    (``BENCH_kernels.json``) instead of print-only CSV lines.  Every record
    is stamped with the measuring backend and host core count."""
    row = f"{name},{us_per_call:.2f},{derived}"
    ROWS.append(row)
    print(row, flush=True)
    if record:
        RECORDS.append({"op": name, "us": round(us_per_call, 3),
                        **_host_fields(), **record})


def write_records(path: str) -> None:
    """Dump the structured rows collected so far as a JSON array."""
    with open(path, "w") as f:
        json.dump(RECORDS, f, indent=2)
        f.write("\n")
    print(f"wrote {len(RECORDS)} records -> {path}", flush=True)


def time_call(fn: Callable, repeats: int = 5, warmup: int = 1) -> float:
    """Median wall time of fn() in seconds."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


# --------------------------------------------------------------------------
# α-β model with the paper's testbed constants (§8.1): g4dn.metal instances,
# 100 Gbps network, T4 GPUs over PCIe3 x8 (≈8 GB/s effective per GPU)
# --------------------------------------------------------------------------

NET_BPS = 100e9 / 8  # bytes/s, 100 Gbps
NET_ALPHA = 30e-6  # per-message latency
PCIE_BPS = 8e9
DRAM_RANDOM_BPS = 2e9  # random-access effective DRAM bandwidth [49]


def net_time(bytes_: float, messages: int = 1) -> float:
    return NET_ALPHA * messages + bytes_ / NET_BPS


def pcie_time(bytes_: float, transfers: int = 1) -> float:
    return 10e-6 * transfers + bytes_ / PCIE_BPS


def dram_random_time(bytes_: float) -> float:
    return bytes_ / DRAM_RANDOM_BPS


def timed_fit(sess, steps: int, warmup: int = 2, serial: bool = False):
    """Warm up a compiled :class:`repro.api.Heta` session, then time
    ``fit(steps)``: returns ``(wall_per_step_s, overlap_fraction)`` over the
    timed steps only (the session's cumulative ``overlap_fraction`` would
    fold in the compile-dominated warmup).  ``fit`` overlaps host and
    device; ``serial`` times a loop of ``Heta.step()`` instead, the serial
    baseline (host stages in line)."""
    def run(n):
        if serial:
            for _ in range(n):
                sess.step()
        else:
            sess.fit(n)

    run(warmup)
    n0 = len(sess.step_times)
    t0 = time.perf_counter()
    run(steps)
    wall = time.perf_counter() - t0
    serial = sum(sess.host_times[n0:]) + sum(sess.step_times[n0:])
    overlap = max(0.0, 1.0 - wall / serial) if serial > 0 else 0.0
    return wall / steps, overlap

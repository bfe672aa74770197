"""The timed-engine *driver throughput* micro (`engine_driver`): host-side
requests retired per second through the scalar oracle vs the vectorized
batched engine, which is what bounds how large a latency x queue-depth
paper sweep is tractable on CPU. The kernels are measured on the chip by
`benchmarks/chip/`."""
from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

Row = Tuple[str, float, str]


# =========================================================================
# Timed-engine driver throughput: scalar oracle vs batched engine
# =========================================================================
def _drive_engine(kind: str, n_requests: int, qlen: int,
                  latency_us: float = 1.0) -> float:
    """Keep the request queue full for `n_requests` loads against the timed
    far-memory model, stepping time in latency-sized epochs; returns
    requests retired per wall-clock second."""
    from repro.configs.base import EngineConfig
    from repro.core.engine import make_engine
    from repro.core.farmem import FarMemoryConfig, FarMemoryModel

    far = FarMemoryModel(FarMemoryConfig.from_latency_us(latency_us))
    eng = make_engine(kind, EngineConfig(queue_length=qlen, granularity=8),
                      far)
    epoch = far.config.base_latency_cycles
    rng = np.random.default_rng(0)
    addrs = rng.integers(0, 4096, size=n_requests) * 8
    zeros = np.zeros(qlen, np.int64)
    sizes = np.full(qlen, 8, np.int64)
    t0 = time.perf_counter()
    issued = retired = 0
    now = 0.0
    while retired < n_requests:
        k = min(qlen - eng.active_requests, n_requests - issued)
        if k:
            if kind == "batched":
                eng.aload_batch(zeros[:k], addrs[issued:issued + k],
                                sizes[:k])
            else:
                for i in range(k):
                    eng.aload(0, int(addrs[issued + i]), 8)
            issued += k
        now += epoch
        eng.advance(now)
        if kind == "batched":
            retired += len(eng.getfin_all())
        else:
            while eng.getfin():
                retired += 1
    return n_requests / (time.perf_counter() - t0)


# Per-port build kwargs for the scheduler-stack axis. `scale` applies to
# both ports (same problem); `vec` shapes only the vector/pipelined port
# (chunk widths and coroutine counts are port properties, not workload
# size). Chase ports (LL/Redis) run their software-pipelined variant.
_PORT_SCALE = {
    "GUPS": dict(table_words=1 << 17),
    "STREAM": dict(n=1 << 18),
    "IS": dict(n_keys=1 << 18),
    "LL": dict(lookups=512, coroutines=64),
}
_PORT_VEC = {
    "GUPS": dict(vec_chunk=64),
    "STREAM": dict(vec_chunk=64, coroutines=2),
    "IS": dict(vec_chunk=64, coroutines=4),
    "HPCG": {},
    "LL": dict(pipeline_k=16),
    "Redis": dict(pipeline_k=16),
}


def _drive_workload_port(wl: str, vector: bool, updates: int,
                         latency_us: float = 1.0, scheduler: str = "auto"):
    """Run a workload port through the full scheduler + batched-engine
    stack; returns ``(req_per_s, RunStats)`` — far-memory requests retired
    per wall-clock second plus the run's host-side observability counters
    (engine entries, rows per entry, wall-µs per entry). This is the
    host-side throughput that bounds paper sweeps — `vector=True` runs
    the AloadVec/AstoreVec (or pipelined-chase) port, `vector=False` PR 1's
    scalar-yield port; `scheduler="batched"` pins the per-command loop,
    the `"auto"` default takes the epoch-fused loop."""
    from repro.amu import REGISTRY, AmuConfig, AmuSession

    kw = dict(_PORT_SCALE.get(wl, {}))
    if wl == "GUPS":
        kw["updates"] = updates
    if vector:
        kw.update(vector=True, **_PORT_VEC.get(wl, {}))
    inst = REGISTRY.build(wl, 0, **kw)
    session = AmuSession(AmuConfig(engine="batched", scheduler=scheduler,
                                   latency_us=latency_us, verify=False))
    session.prepare(inst)       # build + stack construction outside timing
    t0 = time.perf_counter()
    stats = session.execute()
    dt = time.perf_counter() - t0
    assert inst.verify(session.engine.mem)
    return stats.requests / dt, stats


def _entry_counters(stats) -> str:
    """Derived-string fragment for the host-side observability counters."""
    return (f"entries={stats.engine_entries},"
            f"rows_per_entry={stats.rows_per_entry:.1f},"
            f"us_per_entry={stats.us_per_entry:.1f}")


def engine_driver(n_requests: int = 100_000, smoke: bool = False) -> List[Row]:
    rows: List[Row] = []
    if smoke:
        n_requests = 20_000
    for qlen in ((256,) if smoke else (256, 1024)):
        scalar = _drive_engine("scalar", n_requests, qlen)
        batched = _drive_engine("batched", n_requests, qlen)
        rows.append((f"engine/scalar_driver_q{qlen}", 1e6 / scalar,
                     f"req_per_s={scalar:.0f}"))
        rows.append((f"engine/batched_driver_q{qlen}", 1e6 / batched,
                     f"req_per_s={batched:.0f},"
                     f"speedup_vs_scalar={batched / scalar:.2f}x"))
    # vector-command axis: scalar-yield vs AloadVec/pipelined ports through
    # the full scheduler stack (GUPS scaled up so fixed costs don't mask the
    # ratio). The smoke set keeps one representative per port family the CI
    # gate holds a floor for: GUPS (vector RMW), STREAM/IS (zero-copy block
    # ports), LL (pipelined chase). Each vector port runs twice — the
    # per-command BatchScheduler (`_sched_vector`, comparable to earlier
    # sweeps) and the epoch-fused loop (`_sched_vector_fused`, one engine
    # entry per epoch) — so the fusion win (entry collapse, fused_vs_percmd
    # speedup, µs/entry) is visible per workload.
    updates = 16_384 if smoke else 65_536
    wls = (("GUPS", "STREAM", "IS", "LL") if smoke
           else ("GUPS", "STREAM", "IS", "HPCG", "LL", "Redis"))
    for wl in wls:
        s, s_st = _drive_workload_port(wl, vector=False, updates=updates)
        v, v_st = _drive_workload_port(wl, vector=True, updates=updates,
                                       scheduler="batched")
        f, f_st = _drive_workload_port(wl, vector=True, updates=updates)
        rows.append((f"engine/{wl}_sched_scalar_yield", 1e6 / s,
                     f"req_per_s={s:.0f},{_entry_counters(s_st)}"))
        rows.append((f"engine/{wl}_sched_vector", 1e6 / v,
                     f"req_per_s={v:.0f},"
                     f"speedup_vs_scalar_yield={v / s:.2f}x,"
                     f"{_entry_counters(v_st)}"))
        rows.append((f"engine/{wl}_sched_vector_fused", 1e6 / f,
                     f"req_per_s={f:.0f},"
                     f"speedup_vs_scalar_yield={f / s:.2f}x,"
                     f"fused_vs_percmd={f / v:.2f}x,"
                     f"{_entry_counters(f_st)}"))
    return rows


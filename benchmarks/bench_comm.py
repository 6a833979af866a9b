"""Paper Fig. 12: communication cost of the distributed entity partitioning.

Measures the ring-pass (ppermute) wall time on 8 host devices in a
subprocess (BSP supersteps, paper Sec. 6.3) and reports the analytic wire
model: (|p|-1) * |D| elements total, |D| - |D|/|p| sent per node.

Also benchmarks the **device-fused indexed ring** (DESIGN.md #7 addendum)
against the host-driven BSP driver on the same 8-device mesh: the
``fused_ring`` rows record that the fused path compiles to ONE program
(traces=1) executed ONCE per join (executions_per_join=1, device
dispatches=1) while the host driver re-enters Python every round
(dispatches = its chunk-program launches), plus the warm wall time of both.

``--tiny`` (or BENCH_SMOKE=1) shrinks |D| so `make bench-smoke` can keep
this path compiling and running in CI-scale time.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import textwrap

from benchmarks.common import (
    cpu_child_env, emit_bench_json, measure_fused_vs_host, record,
)
from repro.core.distributed import ring_comm_elements

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, time
    sys.path.insert(0, sys.argv[1])
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((8,), ("data",))
    n, dims = int(sys.argv[2]), int(sys.argv[3])
    x = jnp.zeros((n, dims), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("data")))
    perm = [(j, (j + 1) % 8) for j in range(8)]

    def ring(v):
        def body(_, e):
            return jax.lax.ppermute(e, "data", perm)
        return jax.lax.fori_loop(0, 7, body, v)

    f = jax.jit(jax.shard_map(ring, mesh=mesh, in_specs=P("data"), out_specs=P("data")))
    f(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    for _ in range(3):
        f(x).block_until_ready()
    print("RING_US", (time.perf_counter() - t0) / 3 * 1e6)
    """
)

FULL_CELLS = [("Syn16D2M", 40_000, 16), ("SuSy", 40_000, 18)]
TINY_CELLS = [("Syn16D2M", 2_000, 16), ("SuSy", 2_000, 18)]

def run_fused(tiny: bool = False):
    """fused_ring rows: one-program-once contract + fused-vs-host wall time.

    The subprocess (``common.measure_fused_vs_host``) asserts count parity
    and the contract -- traces == 1, device dispatches == 1 per join.
    Emits ``BENCH_fused.json`` for the regression gate: the contracts pin
    the one-trace/one-dispatch discipline and the grid's filter ratio
    (candidates / n^2 -- deterministic for the fixed dataset, so index
    filtering power can never silently rot); the metrics gate the fused
    and host-driven warm wall times within the comparator's slack.
    """
    n, dims = (1_500, 16) if tiny else (12_000, 16)
    contracts: dict = {
        "count_parity": True,           # asserted inside the subprocess
        "pairs_parity": True,           # fused pair SET == host-driven SET
        "fused_traces": 1,
        "fused_dispatches_per_join": 1,
        "fused_pairs_traces": 1,
        "fused_pairs_dispatches_per_join": 1,
    }
    metrics: dict = {}
    info: dict = {"n": n, "dims": dims, "tiny": tiny}
    count_rows, pairs_rows = measure_fused_vs_host(n, dims, [8])
    for p, fused_us, host_us, host_disp, cand in count_rows:
        filter_ratio = cand / float(n * n)
        record(
            f"fused_ring/Syn{dims}D/p={p}", fused_us,
            f"traces=1;executions_per_join=1;device_dispatches=1;"
            f"host_dispatches={host_disp};"
            f"host_us={host_us:.1f};speedup_vs_host={host_us / fused_us:.2f};"
            f"filter_ratio={filter_ratio:.4f}",
        )
        contracts[f"filter_ratio_pct/p={p}"] = round(100.0 * filter_ratio, 2)
        metrics[f"fused_us/p={p}"] = fused_us
        metrics[f"host_us/p={p}"] = host_us
        info[f"host_dispatches/p={p}"] = host_disp
        info[f"speedup_vs_host/p={p}"] = round(host_us / fused_us, 2)
    for p, fp_us, hp_us, retries, npairs in pairs_rows:
        record(
            f"fused_pairs/Syn{dims}D/p={p}", fp_us,
            f"host_pairs_us={hp_us:.1f};"
            f"speedup_vs_host={hp_us / fp_us:.2f};"
            f"overflow_retries={retries};num_pairs={npairs}",
        )
        # the capacity/rank-window seeding must keep warm joins retry-free,
        # and the one-dispatch pairs ring must beat the |p|^2-block host
        # loop at p=8 (the acceptance row for DESIGN.md #7b)
        contracts[f"pair_overflow_retries/p={p}"] = retries
        contracts[f"fused_pairs_faster/p={p}"] = bool(fp_us < hp_us)
        metrics[f"fused_pairs_us/p={p}"] = fp_us
        metrics[f"host_pairs_us/p={p}"] = hp_us
        info[f"num_pairs/p={p}"] = npairs
        info[f"pairs_speedup_vs_host/p={p}"] = round(hp_us / fp_us, 2)
    emit_bench_json("fused", contracts=contracts, metrics=metrics, info=info)


def run(tiny: bool = False):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    for name, n, dims in (TINY_CELLS if tiny else FULL_CELLS):
        out = subprocess.run(
            [sys.executable, "-c", SCRIPT, src, str(n), str(dims)],
            # a CPU rehearsal on 8 simulated devices, not a chip measurement
            capture_output=True, text=True, timeout=600, env=cpu_child_env(),
        )
        if out.returncode != 0:
            raise RuntimeError(f"ring subprocess failed:\n{out.stderr[-2000:]}")
        us = float(out.stdout.split("RING_US")[-1].strip().split()[0])
        elems = ring_comm_elements(n, 8)
        record(
            f"fig12/{name}/p=8", us,
            f"total_elements={elems};bytes={elems * dims * 4};"
            f"per_node_sent={n - n // 8}",
        )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--tiny", action="store_true",
        default=os.environ.get("BENCH_SMOKE") == "1",
        help="CI-scale configuration (also via BENCH_SMOKE=1)",
    )
    tiny = ap.parse_args().tiny
    run(tiny=tiny)
    run_fused(tiny=tiny)

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run + roofline for the PAPER'S technique at pod scale: the entity-
partitioned ring self-join (Sec. 6.3) on the production meshes.

Workload: |D| points x n dims sharded over all chips (joint ring over
("pod","data","model") -- every chip is a ring node, as every GPU is a node
in the paper).  Variants are the hillclimb levers recorded in EXPERIMENTS.md
#Perf (cell C):

  base        fp32 coordinates, compute-then-permute
  overlap     permute issued before compute (round i+1 transport overlaps
              round i compute -- paper Fig. 4's pipeline, at ring scale)
  bf16        bf16 coordinate transport/compute, fp32 accumulation
              (documented approximate variant: ~3 decimal digits)

Usage: python -m repro.launch.selfjoin_dryrun [--points 16777216] [--dims 32]
"""
import argparse     # noqa: E402
import json         # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.distributed import ring_scan  # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_desc  # noqa: E402
from repro.roofline import roofline_terms  # noqa: E402


def ring_fn(mesh, axes, eps, *, variant="base", row_block=2048):
    eps2 = float(eps) ** 2
    axes_t = tuple(axes)

    def local_counts(q, e):
        qc = q
        if variant == "bf16":
            qc, e = q.astype(jnp.bfloat16), e.astype(jnp.bfloat16)
        ne = jnp.einsum("ij,ij->i", e, e, preferred_element_type=jnp.float32)
        blocks = qc.reshape(-1, row_block, q.shape[1])

        def one(qb):
            d2 = (
                jnp.einsum("ij,ij->i", qb, qb, preferred_element_type=jnp.float32)[:, None]
                + ne[None, :]
                - 2.0 * jnp.einsum("id,jd->ij", qb, e, preferred_element_type=jnp.float32)
            )
            return jnp.sum(d2 <= eps2, axis=1, dtype=jnp.int32)

        return jax.lax.map(one, blocks).reshape(-1)

    def body_fn(d_block):
        q = d_block

        def body(_, counts, e):
            return counts + local_counts(q, e)

        counts0 = jax.lax.pcast(
            jnp.zeros(q.shape[0], jnp.int32), axes_t, to="varying"
        )
        # overlap variant: ring_scan issues round r+1's permute before round
        # r's body -- paper Fig. 4's pipeline, at ring scale
        return ring_scan(
            axes_t, body, counts0, q, overlap=(variant == "overlap")
        )

    spec = P(axes_t if len(axes_t) > 1 else axes_t[0])
    return jax.jit(jax.shard_map(body_fn, mesh=mesh, in_specs=spec, out_specs=spec))


def run_cell(points, dims, eps, multi_pod, variant, row_block=2048):
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = mesh.axis_names
    chips = 1
    for a in axes:
        chips *= mesh.shape[a]
    fn = ring_fn(mesh, axes, eps, variant=variant, row_block=row_block)
    d_abs = jax.ShapeDtypeStruct((points, dims), jnp.float32)
    with mesh:
        lowered = fn.lower(d_abs)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    # model flops: |D|^2 pair distances x 3n flops (paper Sec. 4.4), one pass
    model_flops = 3.0 * dims * float(points) ** 2
    rep = roofline_terms(
        arch=f"selfjoin-ring-{variant}", shape=f"D{points}xn{dims}",
        mesh_desc=mesh_desc(mesh), chips=chips,
        hlo_text=compiled.as_text(), model_flops=model_flops,
        memory_analysis=mem,
    )
    d = rep.as_dict()
    d["temp_bytes_per_chip"] = mem.temp_size_in_bytes
    d["arg_bytes_per_chip"] = mem.argument_size_in_bytes
    return d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=16_777_216)  # 2^24, ~2GB fp32 @32d
    ap.add_argument("--dims", type=int, default=32)
    ap.add_argument("--eps", type=float, default=0.08)
    ap.add_argument("--out", default="experiments/selfjoin_ring.json")
    args = ap.parse_args()

    out = {}
    for multi_pod in (False, True):
        for variant in ("base", "overlap", "bf16"):
            tag = f"{'pod2' if multi_pod else 'pod1'}__{variant}"
            d = run_cell(args.points, args.dims, args.eps, multi_pod, variant)
            out[tag] = d
            print(
                f"{tag:16s} comp={d['compute_s']:.3f}s mem={d['memory_s']:.3f}s "
                f"coll={d['collective_s']:.3f}s dom={d['dominant']} "
                f"frac={d['roofline_fraction']:.3f} mfu={d['mfu']:.3f} "
                f"temp={d['temp_bytes_per_chip']/1e9:.2f}GB", flush=True,
            )
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()

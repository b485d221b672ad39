import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import.

"""Roofline dry-run for the paper's OWN workload: the distributed support-
count step at production scale (N=1M transactions x I=2048 items x K=64k
candidates) on the 16x16 mesh — the third hillclimb pair (§Perf).

Variants:
  paper_1d : the paper's decomposition — transactions row-sharded over ALL
             chips, candidates replicated (Hadoop map tasks are 1-D).
  ours_2d  : transactions over 'data', candidates over 'model' (2-D).
  ours_2d_blocked : + fused/blocked containment epilogue (no (N,K) int32
             intermediate — the jnp analogue of the Pallas kernel tiling).
"""

import argparse
import json


def run(variant: str, n=1 << 20, items=2048, k_cands=1 << 16):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.apriori import AprioriConfig, make_count_step
    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import V5E, roofline_terms

    mesh = make_production_mesh()
    if variant == "paper_1d":
        cfg = AprioriConfig(data_axes=("data", "model"), model_axis=None,
                            count_impl="jnp")
    elif variant == "ours_2d":
        cfg = AprioriConfig(data_axes=("data",), model_axis="model", count_impl="jnp")
    elif variant == "ours_2d_blocked":
        cfg = AprioriConfig(data_axes=("data",), model_axis="model",
                            count_impl="jnp_blocked")
    else:
        raise ValueError(variant)

    step = make_count_step(mesh, cfg)
    t_sds = jax.ShapeDtypeStruct((n, items), jnp.int8)
    c_sds = jax.ShapeDtypeStruct((k_cands, items), jnp.int8)
    l_sds = jax.ShapeDtypeStruct((k_cands,), jnp.int32)
    t_sh = NamedSharding(mesh, P(cfg.data_axes, None))
    c_sh = NamedSharding(mesh, P(cfg.model_axis, None))
    l_sh = NamedSharding(mesh, P(cfg.model_axis))
    lowered = jax.jit(step.__wrapped__ if hasattr(step, "__wrapped__") else step,
                      in_shardings=(t_sh, c_sh, l_sh)).lower(t_sds, c_sds, l_sds)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = hlo_analysis.summarize(compiled.as_text())
    # the production mesh is a v5e pod slice, described on host devices
    rl = roofline_terms(hlo["flops"], hlo["hbm_bytes"], hlo["collective_bytes"], V5E)
    model_flops = 2.0 * n * items * k_cands / 256
    return {
        "variant": variant,
        "temp_gb_per_dev": mem.temp_size_in_bytes / 1e9,
        "flops_per_dev": hlo["flops"],
        "hbm_per_dev": hlo["hbm_bytes"],
        "coll_per_dev": hlo["collective_bytes"],
        "compute_s": rl.compute_s,
        "memory_s": rl.memory_s,
        "collective_s": rl.collective_s,
        "dominant": rl.dominant,
        "useful_flops_ratio": model_flops / max(hlo["flops"], 1.0),
        "collective_counts": hlo["collective_counts"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default="all")
    ap.add_argument("--n", type=int, default=1 << 20)
    ap.add_argument("--k", type=int, default=1 << 16)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    variants = ["paper_1d", "ours_2d", "ours_2d_blocked"] if args.variant == "all" else [args.variant]
    recs = [run(v, n=args.n, k_cands=args.k) for v in variants]
    js = json.dumps(recs, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)
    print(js)


if __name__ == "__main__":
    main()

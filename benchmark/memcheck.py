"""The no-chip guard of the size floor: compile a fit configuration's whole
fused program for a DESCRIBED v5e (nothing attached, nothing runs) and print
the compiler's ``memory_analysis`` beside the chip's memory.

``JAX_PLATFORMS=cpu python3 -m benchmark.memcheck --config ml25m-r128``

Run by hand before a cell is asked for; it takes minutes (one scan per shape
group), so it is a script and no test. The bucket shapes come from the
configuration's degree sequences alone — no matrix is generated.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import Counter

import numpy as np


def group_shapes(degrees: np.ndarray, layout: dict) -> list[tuple[int, int, int]]:
    """``(G, B, L)`` of each stacked shape group the program would upload."""
    from albedo_tpu.utils import capacity

    indptr = np.zeros(degrees.size + 1, np.int64)
    np.cumsum(degrees, out=indptr[1:])
    counted = Counter(capacity.bucket_plan_shapes(indptr, **layout))
    return [(g, b, l) for (b, l), g in counted.items()]


def fused_fit_shapes(config: dict, groups_u, groups_i, sharding):
    """Abstract arguments of ``als_init_fit_fused`` for these groups."""
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def groups(shapes):
        return [(sds((g, b), jnp.int32), sds((g, b, l), jnp.int32),
                 sds((g, b, l), jnp.float32), sds((g, b, l), jnp.bool_)) for g, b, l in shapes]

    args = (sds((2,), jnp.uint32), groups(groups_u), groups(groups_i),
            sds((), jnp.float32), sds((), jnp.float32), sds((), jnp.int32))
    kwargs = dict(
        user_landing=sds((config["n_users"],), jnp.int32),
        item_landing=sds((config["n_items"],), jnp.int32),
        n_users=config["n_users"], n_items=config["n_items"], rank=config["rank"],
        solver=config["solver"], cg_steps=config["cg_steps"], gather_dtype=config["gather_dtype"],
    )
    return args, kwargs


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.memcheck")
    parser.add_argument("--config", required=True)
    args = parser.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from albedo_tpu.models.als import ImplicitALS
    from albedo_tpu.ops.als import als_init_fit_fused
    from benchmark import manifest, stars
    from benchmark.peaks import peaks_for

    config = manifest.load_config(manifest.load_manifest(), args.config)
    layout = ImplicitALS()._layout_kwargs()
    n, nnz = (config["n_users"], config["n_items"]), config["nnz"]
    groups_u = group_shapes(stars.degree_sequence(n[0], nnz, config["user_degrees"]), layout)
    groups_i = group_shapes(stars.degree_sequence(n[1], nnz, config["item_degrees"]), layout)
    padded = sum(g * b * l for g, b, l in groups_u + groups_i)
    print(f"{len(groups_u)} + {len(groups_i)} shape groups, {padded} padded entries "
          f"({padded / (2 * nnz):.3f} x the logical entries)", flush=True)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    fit_args, fit_kwargs = fused_fit_shapes(
        config, groups_u, groups_i, SingleDeviceSharding(topo.devices[0]))
    t = time.perf_counter()
    compiled = als_init_fit_fused.lower(*fit_args, **fit_kwargs).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
    hbm = peaks_for("TPU v5e")["hbm_bytes"]
    print(json.dumps({
        "config": args.config, "compile_s": time.perf_counter() - t,
        "argument_bytes": mem.argument_size_in_bytes, "temp_bytes": mem.temp_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes, "total_bytes": total,
        "share_of_16GB": total / hbm,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

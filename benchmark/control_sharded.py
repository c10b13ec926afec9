"""The comparison's control and one fault for a ``fit_sharded`` cell, read on
the chip at the cell's own size:
``python3 -m benchmark.control_sharded --workload <cell> --seeds 1,2,3``.

``control_streamed.py`` cannot take such a cell: it asks for the cell's chips
and builds the one-chip estimator. Neither the control nor the fault runs the
program, and the reference runs on one chip, so this needs ONE chip (a
four-chip cell's control costs a quarter of a run of the cell). For each
seed, in one process, one JSON line with the eight numbers, at each of
``--min-stars``, for:

- ``control_reference_bf16``: the reference put in the program's place and
  computed in bfloat16 throughout;
- ``fault_unchanged``: the seeded init returned as it was.

The program's own readings are the ``compared`` numbers of the cell's
benchmark runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def readings(cell: dict, seed: int, min_stars: list[int]) -> dict:
    import jax.numpy as jnp

    from benchmark import streamed_check
    from benchmark.drivers.fit import fit_seed
    from benchmark.manifest import load_module
    from benchmark.streamed_stars import generate_stars

    config, traffic = cell["config"], cell["traffic"]
    sweeps = traffic["check_sweeps"]
    reference = load_module("reference", config["reference"])
    stars = generate_stars(config, seed)
    degrees = {"user": np.bincount(stars["rows"], minlength=stars["n_users"]),
               "item": np.bincount(stars["cols"], minlength=stars["n_items"])}
    out = {"seed": seed}
    want = streamed_check.reference_fit(reference, stars, config, fit_seed(seed), sweeps)

    def against(name, tables):
        errs = {"user": streamed_check.row_errors(tables[0], want[0]),
                "item": streamed_check.row_errors(tables[1], want[1])}
        out[name] = {
            str(m): {f"{side}_{k}": v for side in ("user", "item")
                     for k, v in streamed_check.numbers_of(errs[side], degrees[side], m).items()}
            for m in min_stars
        }

    against("control_reference_bf16", streamed_check.reference_fit(
        reference, stars, config, fit_seed(seed), sweeps, dtype=jnp.bfloat16))
    init = reference.init_factors(fit_seed(seed), stars["n_users"], stars["n_items"], config["rank"])
    against("fault_unchanged", (np.asarray(init[0]), np.asarray(init[1])))
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.control_sharded")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--min-stars", default=None,
                        help="comma-separated; the configuration's check_min_stars if left out")
    args = parser.parse_args(argv)

    from benchmark import device, manifest

    device.use_compile_cache()
    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    min_stars = ([int(m) for m in args.min_stars.split(",")] if args.min_stars
                 else [cell["config"]["check_min_stars"]])
    print(json.dumps({"device": device.require_chips(1)}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = readings(cell, seed, min_stars)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The comparison's control and faults, read on the chip at a cell's own
size: ``python3 -m benchmark.control --workload <cell> --seeds 1,2,3``.

Not part of a benchmark run. For each seed, in one process (the executables
are shared: every seed has the same shapes), it prints one JSON line with the
numbers ``compare.compare_fit`` gives for:

- ``program``: the timed entry at the configuration's arithmetic (the lower
  reading, as a benchmark run reads it);
- ``control_program_bf16_gather``: the program with its own lower-precision
  path switched on (``gather_dtype="bfloat16"``);
- ``control_reference_bf16``: the reference put in the program's place and
  computed in bfloat16 throughout;
- faults planted in the reference put in the program's place:
  ``fault_unchanged`` (the seeded init returned as it was),
  ``fault_half_left_out`` (every second user row keeps its factor in the last
  half-sweep), ``fault_row_altered`` (one row of each table swapped for its
  neighbour).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def readings(cell: dict, seed: int, dump_dir: str | None = None) -> dict:
    import jax.numpy as jnp

    from benchmark import compare
    from benchmark.drivers import fit as fit_driver
    from benchmark.manifest import load_module
    from benchmark.stars import generate_stars

    config, traffic = cell["config"], cell["traffic"]
    sweeps = traffic["check_sweeps"]
    reference = load_module("reference", config["reference"])
    stars = generate_stars(config, seed)
    want = reference.fit(stars, config, fit_driver.fit_seed(seed), sweeps)
    out = {"seed": seed}

    rows = {"degrees_user": np.bincount(stars["rows"], minlength=stars["n_users"]).astype(np.int32),
            "degrees_item": np.bincount(stars["cols"], minlength=stars["n_items"]).astype(np.int32)}

    def against(name, got):
        out[name] = compare.compare_fit(got[0], got[1], want[0], want[1], stars, config["check_min_stars"])
        if dump_dir and not name.startswith("fault"):
            rows[f"{name}.user"] = compare.row_errors(got[0], want[0]).astype(np.float32)
            rows[f"{name}.item"] = compare.row_errors(got[1], want[1]).astype(np.float32)

    als, matrix = fit_driver.build_program(config, stars, seed)
    got, report = fit_driver.first_sweeps(als, matrix, sweeps)
    out["program_report"] = {k: report[k] for k in ("compile_s", "compile_source", "device_s")}
    against("program", got)
    low, _ = fit_driver.build_program(config, stars, seed, gather_dtype="bfloat16")
    against("control_program_bf16_gather", fit_driver.first_sweeps(low, matrix, sweeps)[0])
    del als, low, matrix
    against("control_reference_bf16",
            reference.fit(stars, config, fit_driver.fit_seed(seed), sweeps, dtype=jnp.bfloat16))

    init = reference.init_factors(fit_driver.fit_seed(seed), stars["n_users"],
                                  stars["n_items"], config["rank"])
    against("fault_unchanged", (np.asarray(init[0]), np.asarray(init[1])))
    before = reference.fit(stars, config, fit_driver.fit_seed(seed), sweeps - 1) \
        if sweeps > 1 else (np.asarray(init[0]), np.asarray(init[1]))
    half = want[0].copy()
    half[::2] = before[0][::2]
    against("fault_half_left_out", (half, want[1]))
    swapped_u, swapped_v = want[0].copy(), want[1].copy()
    swapped_u[7], swapped_v[7] = want[0][8], want[1][8]
    against("fault_row_altered", (swapped_u, swapped_v))
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        np.savez_compressed(os.path.join(dump_dir, f"{cell['name']}.{seed}.npz"), **rows)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.control")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--dump", default=None, help="directory for every row's error, per seed")
    args = parser.parse_args(argv)

    from benchmark import device, manifest

    device.use_compile_cache()
    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    print(json.dumps({"device": device.require_chips(cell["chips"])}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = readings(cell, seed, args.dump)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

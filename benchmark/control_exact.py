"""The comparison's controls and faults for a configuration whose guarantee
is EXACTNESS, read on the chip at the cell's own size:
``python3 -m benchmark.control_exact --workload <cell> --seeds 1,2,3``.

Not part of a benchmark run. ``control.py``'s readings (one JSON line a seed,
the numbers ``compare.compare_fit`` gives against the configuration's exact
reference) with one control more, and the reference followed once a seed:

- ``program``: the timed entry as the configuration states it (exact solve);
- ``control_program_cg``: THE PROGRAM ITSELF with ``solver="cg"``,
  ``cg_steps=3`` in the exact program's place - the approximate solve that
  the repository's other cells run. It must read not correct: a change that
  swaps the exact solve for an iterate must fail this cell;
- ``control_reference_bf16``: the reference put in the program's place and
  computed in bfloat16 throughout (the next precision below the stated one);
- faults planted in the reference put in the program's place:
  ``fault_unchanged``, ``fault_half_left_out``, ``fault_row_altered`` (as
  ``control.py`` plants them).

``correct`` beside each says what the cell's own limits make of it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

CG_CONTROL = {"solver": "cg", "cg_steps": 3}


def readings(cell: dict, seed: int) -> dict:
    import jax.numpy as jnp

    from benchmark import compare
    from benchmark.drivers import fit as fit_driver
    from benchmark.manifest import load_module
    from benchmark.stars import generate_stars

    config, traffic = cell["config"], cell["traffic"]
    if config["solver"] != "cholesky":
        raise ValueError(f"{cell['name']} does not state the exact solve")
    sweeps, fseed = traffic["check_sweeps"], fit_driver.fit_seed(seed)
    reference = load_module("reference", config["reference"])
    out = {"seed": seed, "correct": {}, "seconds_by": {}}
    clock = time.perf_counter()
    stars = generate_stars(config, seed)

    def lap(name):
        nonlocal clock
        now = time.perf_counter()
        out["seconds_by"][name], clock = round(now - clock, 2), now

    def follow(n_sweeps, **kw):
        """The reference's tables after ``n_sweeps`` sweeps (0: its seeded init)."""
        if n_sweeps == 0:
            init = reference.init_factors(fseed, stars["n_users"], stars["n_items"], config["rank"])
            return np.asarray(init[0], np.float32), np.asarray(init[1], np.float32)
        return reference.fit(stars, config, fseed, n_sweeps, **kw)

    lap("generate")
    want = follow(sweeps)
    lap("reference")

    def against(name, got):
        out[name] = compare.compare_fit(got[0], got[1], want[0], want[1], stars, config["check_min_stars"])
        out["correct"][name] = compare.judge(out[name], config["check_limits"])[0]

    for name, overrides in (("program", {}), ("control_program_cg", CG_CONTROL)):
        als, matrix = fit_driver.build_program(config, stars, seed, **overrides)
        got, report = fit_driver.first_sweeps(als, matrix, sweeps)
        out[f"{name}_report"] = {k: report.get(k) for k in (
            "compile_s", "compile_source", "device_s", "mode", "exact_systems_per_sweep", "exact_system_share")}
        against(name, got)
        del als, matrix
        lap(name)
    against("control_reference_bf16", follow(sweeps, dtype=jnp.bfloat16))
    lap("control_reference_bf16")
    against("fault_unchanged", follow(0))
    before = follow(sweeps - 1)
    half = want[0].copy()
    half[::2] = before[0][::2]
    against("fault_half_left_out", (half, want[1]))
    swapped_u, swapped_v = want[0].copy(), want[1].copy()
    swapped_u[7], swapped_v[7] = want[0][8], want[1][8]
    against("fault_row_altered", (swapped_u, swapped_v))
    lap("faults")
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark.control_exact")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    args = parser.parse_args(argv)

    from benchmark import device, manifest

    device.use_compile_cache()
    cell = manifest.resolve_cell(manifest.load_manifest(), args.workload)
    print(json.dumps({"device": device.require_chips(cell["chips"])}), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        row = readings(cell, seed)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One cell, one run: ``python3 -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

Loads the cell's files by the names ``BENCHMARK.json`` gives them, refuses
any machine without the chips the cell asks for, hands the cell to the driver
its traffic file names, and prints the result line only if it meets the
contract (``lastline.py``). Everything else goes to standard error.
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv: list[str] | None = None) -> int:
    started = time.perf_counter()
    parser = argparse.ArgumentParser(prog="benchmark.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    from benchmark import lastline, manifest
    from benchmark.device import NoChip, use_compile_cache

    mf = manifest.load_manifest()
    cell = manifest.resolve_cell(mf, args.workload)
    traced = bool(args.trace)
    expected = manifest.metrics_for(mf, args.workload, traced)

    use_compile_cache()
    driver = manifest.load_module("drivers", cell["traffic"]["driver"])
    try:
        result = driver.run(cell, args.seed, args.seconds, traced, started, expected)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    line = lastline.build_line(**result)
    compared_text = "\n".join(
        f"compared {name}: {c['value']!r} (limit {c['limit']!r})"
        for name, c in result["compared"].items()
    ) + f"\ncorrect: {result['correct']}"
    try:
        lastline.emit(line, expected, traced, compared_text)
    except lastline.LineError as e:
        print(f"the result line does not meet the contract: {e}", file=sys.stderr)
        print(compared_text, file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())

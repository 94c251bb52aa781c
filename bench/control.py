#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``, at a cell's size.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed: the run's graph, the plain reference's exact answer, and the
control, which is the same reference computed one precision lower (its
per-embedding values summed in bfloat16 on the chip, ``ixbench.compare``).
Each is judged by the harness's comparison; the control has to come out
not correct. One JSON line per seed. The benchmark's own runs never run
this. Without a TPU it exits with 1.
"""
import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]

from ixbench import compare, graphs  # noqa: E402
from ixbench.harness import REFERENCE_DIR, find_cell, load_module  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = find_cell(ROOT, args.workload)
    reference = load_module(ROOT / REFERENCE_DIR
                            / f"{cell.traffic['query']}.py")
    import jax
    if jax.devices()[0].platform != "tpu":
        print("[control] no TPU", file=sys.stderr)
        return 1
    limit = float(cell.traffic["answer_gap_limit"])
    for seed in args.seeds:
        values = reference.values(graphs.make_graph(cell.config, seed))
        want = compare.exact_answer(values)
        ctrl = compare.control_answer(values)
        gap = compare.answer_gap([ctrl], want)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "reference": want, "control": ctrl,
                          "control_gap": gap, "limit": limit,
                          "control_correct": gap <= limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set the training cells' correctness limits, on the chip.

For each seed it runs the cell as the benchmark does (with a window of one
step) and records the three readings of the program against the float32
reference. For the first ``--faults`` seeds it also reads, against the same
reference, the control (the reference with float8 matmuls put in the
program's place) and the half-batch fault (the reference on half of each
batch's rows, its mean taken over those). A step that returns its state
unchanged reads 1 on the gradient and change readings by construction.

    python3 bench/calibrate_train.py --workload train-internlm2-s2k \\
        --seeds 101,102,103 --faults 3

One JSON line per reading goes to ``chiprun_out/calibrate_<workload>.jsonl``
and to standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args()
    cell = harness.find_cell(args.workload)
    harness.require_chips(cell.chips)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from bench.reference import lm_train as reference
    drv = harness.driver(cell)
    t = cell.traffic
    arch = drv.reference_arch(cell.config)
    out = ROOT / "chiprun_out" / f"calibrate_{args.workload}.jsonl"
    out.parent.mkdir(exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    with out.open("a") as f:
        def emit(row):
            line = json.dumps(row)
            print(line, flush=True)
            f.write(line + "\n")
            f.flush()

        for i, seed in enumerate(seeds):
            t0 = time.perf_counter()
            window = harness.Window(0.0)
            outcome = drv.run(cell, seed, 0.0, window)
            ref = outcome.facts["reference"]
            emit({"seed": seed, "side": "program", "s": time.perf_counter() - t0,
                  "readings": {k: v for k, v, _ in outcome.checks},
                  "losses": outcome.facts["program"]["losses"],
                  "reference_losses": ref["losses"]})
            if i >= args.faults:
                continue
            for side, kw in (("control_fp8", {"matmul": "fp8"}),
                             ("half_batch", {"rows": slice(0, t["batch"] // 2)})):
                t0 = time.perf_counter()
                other = reference.run(arch, t["optimizer"], t["batch"], t["seq"], seed,
                                      n_steps=drv.WARM_STEPS, **kw)
                emit({"seed": seed, "side": side, "s": time.perf_counter() - t0,
                      "readings": drv.compare(other, ref), "losses": other["losses"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())

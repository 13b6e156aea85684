"""Readings that set the latent-attention MoE cell's correctness limits, on
the chip (``bench/calibrate_train.py``'s protocol, with this model's
reference and one more fault).

For each seed it runs the cell as the benchmark does (with a window of one
step) and records the three readings of the program against the float32
reference. For the first ``--faults`` seeds it also reads, against the same
reference: the control (the reference with float8 matmuls), the half-batch
fault (the reference on half of each batch's rows), and the capacity fault
(the program with the sharded path's capacity dispatch at factor 1.25 put
in place of the drop-free routed experts, so that rows past an expert's
capacity are dropped).

    python3 bench/calibrate_mla_moe.py --workload train-moonlight-s8k \\
        --seeds 101,102,103 --faults 3

One JSON line per reading goes to standard output and to
``bench_out/calibrate_<workload>.jsonl``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from unittest import mock  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402

CAPACITY_FACTOR = 1.25


def capacity_dispatch(params, x, *, top_k, scoring, renormalize, routed_scale):
    """The drop-free layer's signature over the capacity dispatch
    (``layers.moe._dispatch_compute``): each held expert takes at most
    ``ceil(N * k * 1.25 / E)`` rows, the rest pass by."""
    import jax.numpy as jnp
    from repro.layers import moe
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    topw, topi, aux = moe._route(xf, params, top_k, scoring, renormalize, routed_scale)
    n_experts = params["w_router"].shape[1]
    capacity = moe.moe_capacity(B * S, top_k, n_experts, CAPACITY_FACTOR)
    out = moe._dispatch_compute(xf, topw.astype(xf.dtype), topi, params["w_gate"],
                                params["w_up"], params["w_down"], 0, n_experts, capacity)
    return out.reshape(B, S, D), aux, jnp.zeros((2,), jnp.int32)


def capacity_fault():
    """A context in which the model's MoE layers drop rows past capacity."""
    import repro.models.lm as lm
    return mock.patch.object(lm, "moe_apply_local", capacity_dispatch)


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
    drv = harness.driver(cell)
    t = cell.traffic
    arch = drv.reference_arch(cell.config)
    out = ROOT / "bench_out" / f"calibrate_{args.workload}.jsonl"
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
            outcome = drv.run(cell, seed, 0.0, harness.Window(0.0))
            ref = outcome.facts["reference"]
            emit({"seed": seed, "side": "program", "s": time.perf_counter() - t0,
                  "readings": {k: v for k, v, _ in outcome.checks},
                  "losses": outcome.facts["program"]["losses"],
                  "reference_losses": ref["losses"],
                  "memory_peak_bytes": outcome.memory_peak_bytes})
            if i >= args.faults:
                continue
            for side, kw in (("control_fp8", {"matmul": "fp8"}),
                             ("half_batch", {"rows": slice(0, t["batch"] // 2)})):
                t0 = time.perf_counter()
                other = drv.reference.run(arch, t["optimizer"], t["batch"], t["seq"], seed,
                                          n_steps=drv.WARM_STEPS, **kw)
                emit({"seed": seed, "side": side, "s": time.perf_counter() - t0,
                      "readings": drv.compare(other, ref), "losses": other["losses"]})
            t0 = time.perf_counter()
            with capacity_fault(), mock.patch.object(drv.reference, "run",
                                                     lambda *a, **kw: ref):
                faulty = drv.run(cell, seed, 0.0, harness.Window(0.0))
            emit({"seed": seed, "side": "capacity_1.25", "s": time.perf_counter() - t0,
                  "readings": {k: v for k, v, _ in faulty.checks},
                  "losses": faulty.facts["program"]["losses"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())

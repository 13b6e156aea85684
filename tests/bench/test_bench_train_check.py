"""The training cell's correctness check, driven on the CPU at a small size.

Each test runs the whole cell as ``bench/run.py`` does, past its look for a
chip: ``train_loop`` through the adapter, the window, the float32 reference
and the comparison. Underneath, the timed path is left sound or broken in
one of the ways a training cell can fail; the control puts the float8
reference in the program's place. Widths and depth are cut so that a test
run holds it; the limits are the cell's own.
"""
import json
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness
from bench.reference import lm_train as reference

# the cell's head size, a quarter of its width, one layer: at this size the
# sound readings lie within the cell's limits (the gaps grow as widths shrink)
SMALL = dict(hidden_size=512, intermediate_size=2048, num_attention_heads=4,
             num_key_value_heads=2, head_dim=128, num_hidden_layers=1, vocab_size=512)
# the cell's batch at a short sequence; the model's dense attention path, as
# in the cell
CELLS = {"train-internlm2-s2k": dict(batch=4, seq=64)}
SEED = 2**31 + 11


def _cell(name):
    cell = harness.find_cell(name)
    cell.config.update(SMALL)
    cell.traffic.update(CELLS[name], workers=2)
    return cell


def _broken(real, fault):
    def make(*args, **kw):
        fn, in_sh, out_sh, donate = real(*args, **kw)
        if fault == "unchanged":
            def step(p, s, b):
                return p, s, fn(p, s, b)[2]
            return step, in_sh, out_sh, ()
        def step(p, s, b):          # half the batch left out, the mean over the rest
            return fn(p, s, jax.tree.map(lambda x: x[: x.shape[0] // 2], b))
        return step, (in_sh[0], in_sh[1], None), out_sh, donate
    return make


def _run(cell):
    return harness.run_cell(cell, SEED, 0.3, False, time.perf_counter(), jax.devices()[:1])


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _run(_cell(name))
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks" and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, name, fault):
    import repro.launch.train as train_mod
    monkeypatch.setattr(train_mod, "make_train_step",
                        _broken(train_mod.make_train_step, fault))
    res = _run(_cell(name))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_float8_control_is_not_correct(name):
    cell = _cell(name)
    drv = harness.driver(cell)
    t = cell.traffic
    arch = drv.reference_arch(cell.config)
    ref = reference.run(arch, t["optimizer"], t["batch"], t["seq"], SEED, drv.WARM_STEPS)
    control = reference.run(arch, t["optimizer"], t["batch"], t["seq"], SEED,
                            drv.WARM_STEPS, matmul="fp8")
    readings = drv.compare(control, ref)
    assert any(readings[k] > lim for k, lim in t["limits"].items()), readings


def _bench_run(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-internlm2-s2k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(cwd)})


def test_no_chip_exits_nonzero_without_a_result():
    r = _bench_run(harness.ROOT)
    assert r.returncode != 0 and "{" not in r.stdout, r.stdout
    assert "TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    for p in json.loads((harness.ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    r = _bench_run(tmp_path)
    assert r.returncode != 0 and "{" not in r.stdout, r.stdout

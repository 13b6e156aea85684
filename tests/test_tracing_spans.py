"""The program's spans and counters (``repro.core.tracing.span``/``count``):
what each record holds, the ring's bound, the compile counter, and the
spans of a training loop as the profiler's own trace holds them."""
import glob
import subprocess
import sys
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.core import tracing
from repro.core.tracing import TaskStream, count, new_run, span

SRC = Path(__file__).resolve().parents[1] / "src"


@pytest.fixture
def ring(monkeypatch):
    ring = TaskStream(capacity=64)
    monkeypatch.setattr(tracing, "SPANS", ring)
    return ring


def _by_name(ring):
    return {r["name"]: r for r in ring.since(0)}


def test_children_inherit_step_and_run_and_name_their_parent(ring):
    run = new_run()
    with span("train.step", step=7, run=run) as outer:
        with span("train.next_batch") as mid:
            with span("data.wait", x=1):
                count("hits", 3, where="here")
        with span("train.sync"):
            pass
    with span("after"):
        pass
    recs = _by_name(ring)
    assert [r["name"] for r in ring.since(0)] == [
        "hits", "data.wait", "train.next_batch", "train.sync", "train.step", "after"]
    assert recs["train.step"]["parent"] is None and recs["train.step"]["id"] == outer.id
    assert recs["train.next_batch"]["parent"] == outer.id
    assert recs["data.wait"]["parent"] == mid.id and recs["data.wait"]["args"] == {"x": 1}
    assert recs["hits"]["parent"] == recs["data.wait"]["id"]
    assert recs["hits"]["value"] == 3 and recs["hits"]["args"] == {"where": "here"}
    for name in ("train.step", "train.next_batch", "data.wait", "train.sync", "hits"):
        assert (recs[name]["step"], recs[name]["run"]) == (7, run), name
    assert (recs["after"]["step"], recs["after"]["run"], recs["after"]["parent"]) == (None, None, None)
    assert recs["train.step"]["t0"] <= recs["data.wait"]["t0"] <= recs["data.wait"]["t1"] \
        <= recs["train.sync"]["t0"] <= recs["train.step"]["t1"]
    assert outer.t1 == recs["train.step"]["t1"]
    assert new_run() > run


def test_another_thread_has_its_own_stack(ring):
    with span("train.step", step=1, run=new_run()):
        t = threading.Thread(target=lambda: span("task.x").__enter__().__exit__(None, None, None))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    rec = _by_name(ring)["task.x"]
    assert (rec["parent"], rec["step"], rec["run"]) == (None, None, None)
    assert rec["thread"] != _by_name(ring)["train.step"]["thread"]


def test_span_is_recorded_when_an_exception_leaves_it(ring):
    with pytest.raises(KeyError):
        with span("train.step", step=2, run=new_run()):
            with span("train.log"):
                raise KeyError("window closed")
    recs = _by_name(ring)
    assert recs["train.log"]["error"] == "KeyError"
    assert recs["train.step"]["error"] == "KeyError"
    with span("next"):
        pass
    assert _by_name(ring)["next"]["parent"] is None     # the stack was unwound
    assert recs["train.log"]["t1"] >= recs["train.log"]["t0"]


def test_ring_keeps_the_newest_and_counts_the_dropped(monkeypatch):
    ring = TaskStream(capacity=4)
    monkeypatch.setattr(tracing, "SPANS", ring)
    for i in range(10):
        with span(f"s{i}"):
            pass
    assert len(ring) == 4 and ring.dropped == 6
    assert [r["name"] for r in ring.since(0)] == ["s6", "s7", "s8", "s9"]
    assert tracing.SPAN_RING >= 51 / 0.44 * 10


def test_repro_core_imports_no_jax():
    code = ("import sys; import repro.core; from repro.core.tracing import annotate, span, count\n"
            "with span('a', step=1, run=1):\n    count('c')\n"
            "with annotate('task.x', task_id=1):\n    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=60, env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr


def test_a_new_shape_counts_one_compile_tagged_with_its_step(ring):
    import jax
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    run = new_run()
    with span("train.step", step=5, run=run):
        f(np.ones((3, 37), np.float32)).block_until_ready()
    with span("train.step", step=6, run=run):
        f(np.ones((3, 37), np.float32)).block_until_ready()
    compiles = [r for r in ring.since(0) if r["name"] == "jit.compiles"]
    assert [(r["step"], r["run"], r["value"]) for r in compiles] == [(5, run, 1)]
    secs = [r for r in ring.since(0) if r["name"] == "jit.compile_s"]
    assert secs and all(r["step"] == 5 and r["value"] > 0 for r in secs)
    (step5,) = [r for r in ring.since(0) if r["name"] == "train.step" and r["step"] == 5]
    assert sum(r["value"] for r in secs) <= step5["t1"] - step5["t0"] + 1e-3


def _events(line):
    """The program's spans on one line of the trace: (name, start, end, args)."""
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for e in line.events if e.name.startswith(("train.", "data.", "task."))]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_trace_holds_the_training_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData
    from repro.configs import get_config
    from repro.launch.train import train_loop

    cfg = get_config("qwen3-0.6b", reduced=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = train_loop(cfg, steps=4, batch=2, seq=16, workers=2, seed=5, log_every=1)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    lines = [_events(ln) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for ln in plane.lines]
    (main,) = [evs for evs in lines if any(e[0] == "train.step" for e in evs)]
    steps = [e for e in main if e[0] == "train.step"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2, 3]
    run = steps[0][3]["run"]
    for name in ("train.put", "train.dispatch", "train.sync", "train.log"):
        evs = [e for e in main if e[0] == name]
        assert len(evs) == 4, name
        for e, st in zip(evs, steps):
            assert _inside(e, st) and (e[3]["step"], e[3]["run"]) == (st[3]["step_num"], run)
    nexts = [e for e in main if e[0] == "train.next_batch"]
    waits = [e for e in main if e[0] == "data.wait"]
    assert len(nexts) == 3
    for nb in nexts:
        assert sum(_inside(w, nb) for w in waits) == 1
        assert any(_inside(nb, st) for st in steps)
    workers = [evs for evs in lines if evs is not main
               and any(e[0] == "task.data_prefetch" for e in evs)]
    assert workers
    assert all("task_id" in e[3] for evs in workers for e in evs if e[0] == "task.data_prefetch")
    assert not any(e[0] == "task.data_prefetch" for e in main)
    # the task bodies are on the trace's clock alone, not in the span ring
    assert not any(r["name"].startswith("task.") for r in tracing.SPANS.since(0))
    # the returned rate is that of the steps after the first, from their spans
    recs = [r for r in tracing.SPANS.since(0) if r["name"] == "train.step" and r["run"] == run]
    assert [r["step"] for r in recs] == [0, 1, 2, 3]
    assert out["tokens_per_s"] == pytest.approx(3 * 2 * 16 / (recs[-1]["t1"] - recs[0]["t1"]))

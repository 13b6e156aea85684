"""The readers of the program's spans and counters, on a made-up span ring:
each reads its number from the last run's window steps, raises where those
are not the steps the window counted or the ring lacks the run, and reads
nothing from a program that keeps no span ring."""
import pytest

from bench import harness
from repro.core import tracing
from repro.core.tracing import TaskStream

CELL = "train-internlm2-s2k"
READERS = ["host_gap_ms.train", "prefetch_wait_ms.train", "compiles.train",
           "init_s.train", "compile_s.train"]
# window steps 3, 4, 5 (WARM_STEPS is 3), step k running from 10k to 10k + 1:
# dispatched by 10k + 0.2, synced at 10k + 0.9, a 4 ms wait on the prefetch
EXPECTED = {"host_gap_ms.train": 9300.0, "prefetch_wait_ms.train": 4.0,
            "compiles.train": 2.0, "init_s.train": 2.5, "compile_s.train": 1.75}


def _span(ring, name, t0, t1, step=None, run=1):
    ring.append("span", name=name, t0=t0, t1=t1, thread=1, id=0, parent=None,
                step=step, run=run, args={}, error=None)


def _count(ring, name, value, step, run=1):
    ring.append("count", name=name, value=value, t=0.0, thread=1, parent=None,
                step=step, run=run, args={})


def _ring(run=1, steps=6, scale=1.0):
    ring = TaskStream(capacity=256)
    _span(ring, "train.init", -5.0, -5.0 + 2.5 * scale, run=run)
    _count(ring, "jit.compile_s", 9.0, None, run=run)          # init: before any step
    _count(ring, "jit.compile_s", 1.5 * scale, 0, run=run)
    _count(ring, "jit.compiles", 1, 0, run=run)
    _count(ring, "jit.compile_s", 0.25 * scale, 2, run=run)
    _count(ring, "jit.compiles", 2, 4, run=run)
    _count(ring, "jit.compile_s", 0.5, 4, run=run)              # in the window
    for k in range(steps):
        t = 10.0 * k
        _span(ring, "train.step", t, t + 1.0, step=k, run=run)
        _span(ring, "train.dispatch", t + 0.1, t + 0.2 * scale, step=k, run=run)
        _span(ring, "data.wait", t + 0.3, t + 0.3 + 0.004 * scale, step=k, run=run)
        _span(ring, "train.sync", t + 0.5, t + 0.9, step=k, run=run)
    _span(ring, "task.data_prefetch", 0.0, 1.0, run=None)
    return ring


def _ctx(window_steps):
    cell = harness.find_cell(CELL)
    t = cell.traffic
    outcome = harness.Outcome(metrics={}, attempted=window_steps, failed=0, checks=[],
                              memory_peak_bytes=None,
                              facts={"tokens": window_steps * t["batch"] * t["seq"],
                                     "seq": t["seq"]})
    return harness.Context(cell, outcome, None, "TPU v5 lite", 1)


def _read(name, ctx):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read(ctx)


def _merge(*rings):
    ring = TaskStream(capacity=1024)
    for r in rings:
        for rec in r.since(0):
            kind = rec.pop("kind")
            rec.pop("seq")
            ring.append(kind, **rec)
    return ring


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_last_run_of_a_made_up_ring(monkeypatch, name):
    # an earlier run, with other numbers, comes first in the ring
    monkeypatch.setattr(tracing, "SPANS", _merge(_ring(run=1, steps=9, scale=3.0), _ring(run=2)))
    assert _read(name, _ctx(window_steps=3)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_raises_where_the_window_counted_other_steps(monkeypatch, name):
    monkeypatch.setattr(tracing, "SPANS", _ring())
    with pytest.raises(ValueError, match="window steps"):
        _read(name, _ctx(window_steps=4))


@pytest.mark.parametrize("name", READERS)
def test_reader_is_silent_without_a_span_ring(monkeypatch, name):
    monkeypatch.delattr(tracing, "SPANS")
    assert _read(name, _ctx(window_steps=3)) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_raises_on_a_ring_without_the_run(monkeypatch, name):
    monkeypatch.setattr(tracing, "SPANS", TaskStream(capacity=8))
    with pytest.raises(ValueError, match="no training run"):
        _read(name, _ctx(window_steps=3))
    # a bound that evicted the run's train.init
    small = TaskStream(capacity=20)
    for rec in _ring().since(0):
        small.append(rec.pop("kind"), **{k: v for k, v in rec.items() if k != "seq"})
    monkeypatch.setattr(tracing, "SPANS", small)
    with pytest.raises(ValueError, match="train.init is not in the span ring"):
        _read(name, _ctx(window_steps=3))
    # a run with no window step
    monkeypatch.setattr(tracing, "SPANS", _ring(steps=3))
    with pytest.raises(ValueError, match="0 window steps"):
        _read(name, _ctx(window_steps=0))


def test_readers_read_a_run_of_the_cell_on_the_cpu():
    """The cell at a quarter of its widths, one layer: the adapter's window
    and the program's spans agree, and no step of the window compiles."""
    cell = harness.find_cell(CELL)
    cell.config.update(hidden_size=512, intermediate_size=2048, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=128, num_hidden_layers=1,
                       vocab_size=512)
    cell.traffic.update(batch=4, seq=64, workers=2)
    window = harness.Window(0.3)
    outcome = harness.driver(cell).run(cell, 2**31 + 29, 0.3, window)
    assert outcome.attempted >= 1
    ctx = harness.Context(cell, outcome, None, "cpu", 1)
    values = {name: _read(name, ctx) for name in READERS}
    assert values["compiles.train"] == 0.0, values
    assert all(values[n] > 0 for n in READERS if n != "compiles.train"), values

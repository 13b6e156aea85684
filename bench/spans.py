"""What the readers of the program's own spans and counters share.

The program records them in one ring, ``repro.core.tracing.SPANS``; a
program without it gives no reading, and a ring that lacks the run's
records is an error. Each training call is one run;
its steps numbered ``WARM_STEPS`` and up are the window's steps, as the
loop ends when the window closes. Their number must be the number of
steps the adapter counted, or a reading would mix in steps from outside
the window (or have lost some to the ring's bound).
"""
from __future__ import annotations

import importlib
from typing import Any, Dict, List, Optional

from bench import harness


class Run:
    """The records of one run: ``window`` its window's step numbers."""

    def __init__(self, records: List[Dict[str, Any]], warm: int):
        self.records, self.warm = records, warm
        self.window = sorted(r["step"] for r in self.spans("train.step")
                             if r["step"] >= warm)

    def spans(self, name: str) -> List[Dict[str, Any]]:
        return [r for r in self.records if r["kind"] == "span" and r["name"] == name]

    def counts(self, name: str) -> List[Dict[str, Any]]:
        return [r for r in self.records if r["kind"] == "count" and r["name"] == name]

    def by_step(self, name: str) -> Dict[int, Dict[str, Any]]:
        return {r["step"]: r for r in self.spans(name)}


def last_run(ctx: harness.Context) -> Optional[Run]:
    """The last run's records, or None where the program keeps no span ring
    (it predates it). Raises where the ring holds no training run, where the
    run's ``train.init`` was evicted by the ring's bound, or where the
    window's steps are not those counted."""
    ring = getattr(importlib.import_module("repro.core.tracing"), "SPANS", None)
    if ring is None:
        return None
    records = ring.since(0)
    runs = [r["run"] for r in records if r.get("run") is not None]
    if not runs:
        raise ValueError(f"the span ring holds no training run ({len(records)} records, "
                         f"{ring.dropped} dropped): no train.step or train.init span")
    last = max(runs)
    run = Run([r for r in records if r.get("run") == last],
              harness.driver(ctx.cell).WARM_STEPS)
    if not run.spans("train.init"):
        raise ValueError(f"run {last}'s train.init is not in the span ring "
                         f"({ring.dropped} records dropped by its bound)")
    t = ctx.cell.traffic
    counted = ctx.outcome.facts["tokens"] / (t["batch"] * t["seq"])
    if not run.window or len(run.window) != counted:
        raise ValueError(f"the last run has {len(run.window)} window steps in the span "
                         f"ring (steps {run.window[:1]}..{run.window[-1:]}); "
                         f"the window counted {counted:g}")
    return run

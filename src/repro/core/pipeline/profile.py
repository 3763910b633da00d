"""Prover spans and counters: the program's one recorder.

The aggregated prover runs in well-separated phases (witness stacking,
the commitment phase, challenge derivation, the bucketed matmul
sumchecks, the anchor sumcheck, and the step-(c) openings); attributing
prove time to phases is what lets a perf PR claim "the win came from the
commitment batching" instead of pointing at end-to-end noise.

A `PhaseProfile` records spans: ``with span("commit"):`` notes the
span's name, its parent, its ``perf_counter`` start and end, the host
reads counted inside it and the window id every span of one prove
shares, and opens a ``jax.profiler.TraceAnnotation("zkdl/commit",
window=...)``, so the same span sits on the profiler's clock beside the
device's programs.  The prover's programs are AOT-compiled
(`core/execache.py`) and never re-trace, so a ``jax.named_scope`` at a
call site would reach nothing; a host span is what the trace can show.

The active profile is thread-local: `PhaseProfile.activate` makes it
the target of every `span` and `host_read` on this thread until it
exits.  A profile activated inside another hands its records to the
outer one on exit, under the outer's innermost open span (a prove
inside the service's window, or inside the warm-up).  With no active
profile a span still annotates the trace and records nothing.

The host-read counter is booked to the innermost open span;
`field.decode`, `group.decode_group` and `group.decode_group_many`
count every device-to-host read (the Fiat-Shamir syncs).

Always on: a span costs a few microseconds with no profiler session.
``ProofSession.last_profile``, the ``benchmarks/agg_steps.py`` rows,
``BENCH_prover_phases.json`` and ``ProverService.window_profiles`` read
it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

#: trace names are ``zkdl/<span>``
TRACE_PREFIX = "zkdl/"

# canonical phase order (rendering / JSON emission): the children of a
# `prove` span
PHASES = ("stack", "commit", "challenges", "matmul", "anchor", "openings")

# sub-phases of the dominant `openings` phase: claim combination (the
# per-tensor rho folds, the direct-sum assembly AND the merged-vector
# concatenation), the zkReLU validity statement/table preparation
# (challenge draws + the Pallas/jnp table kernel), the wait for the
# validity statements' H-basis inversions (`batch_inv`, lane-blocked),
# the merged pair-IPA's L/R round loop and its final Schnorr opening.
# Tracked apart from `phases_s` so `accounted_s` (which the --smoke
# attribution check compares against total_s) never double counts.
SUB_PHASES = ("claim-combine", "zkrelu-validity", "zkrelu-inverse",
              "ipa-rounds", "sigma")

_local = threading.local()


def active() -> Optional["PhaseProfile"]:
    """This thread's active profile, or None."""
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def span(name: str):
    """A span of the active profile; with none, only a trace annotation."""
    prof = active()
    if prof is None:
        return TraceAnnotation(TRACE_PREFIX + name)
    return prof.span(name)


def host_read() -> None:
    """Count one device-to-host read against the innermost open span."""
    prof = active()
    if prof is not None and prof._open:
        prof.records[prof._open[-1]]["host_reads"] += 1


def _seconds(rec: dict) -> float:
    return rec["end"] - rec["start"]


@dataclasses.dataclass
class PhaseProfile:
    """Span records of one prove (or of a window, or of the warm-up).

    ``records`` holds one dict per span, in the order the spans opened:
    ``name``, ``parent`` (the index of the parent record, None at the
    top), ``start`` and ``end`` (``perf_counter`` seconds),
    ``host_reads`` (reads booked while it was the innermost span) and
    ``window``: the id given, else the one of the profile this one is
    activated inside (the service's window number, or "warm")."""

    window: object = None
    records: List[dict] = dataclasses.field(default_factory=list)
    _open: List[int] = dataclasses.field(default_factory=list, repr=False)

    @contextlib.contextmanager
    def activate(self):
        stack = _local.__dict__.setdefault("stack", [])
        outer = stack[-1] if stack else None
        if self.window is None and outer is not None:
            self.window = outer.window
        stack.append(self)
        try:
            yield self
        finally:
            stack.pop()
            if outer is not None:
                outer._adopt(self.records)

    @contextlib.contextmanager
    def span(self, name: str):
        rec = {"name": name,
               "parent": self._open[-1] if self._open else None,
               "start": time.perf_counter(), "end": None,
               "host_reads": 0, "window": self.window}
        self.records.append(rec)
        self._open.append(len(self.records) - 1)
        try:
            with TraceAnnotation(TRACE_PREFIX + name, window=self.window):
                yield
        finally:
            rec["end"] = time.perf_counter()
            self._open.pop()

    def _adopt(self, records: List[dict]) -> None:
        base, top = len(self.records), (self._open[-1] if self._open
                                        else None)
        for rec in records:
            parent = rec["parent"]
            self.records.append(dict(
                rec, window=self.window,
                parent=top if parent is None else base + parent))

    def _children_s(self, parent_name: str) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for rec in self.records:
            p = rec["parent"]
            if p is not None and self.records[p]["name"] == parent_name:
                out[rec["name"]] = out.get(rec["name"], 0.0) + _seconds(rec)
        return out

    @property
    def phases_s(self) -> Dict[str, float]:
        """Seconds of each phase: the children of the `prove` spans."""
        return self._children_s("prove")

    @property
    def sub_s(self) -> Dict[str, float]:
        """Seconds of each sub-phase: the children of `openings`."""
        return self._children_s("openings")

    @property
    def total_s(self) -> float:
        """Seconds inside the `prove` spans."""
        return sum(_seconds(r) for r in self.records if r["name"] == "prove")

    @property
    def accounted_s(self) -> float:
        """Sum of the recorded top-level phases (should be ~total_s; the
        residual is proof-object assembly and python glue)."""
        return sum(self.phases_s.values())

    def as_dict(self) -> Dict:
        phases = self.phases_s
        ordered = {k: phases[k] for k in PHASES if k in phases}
        ordered.update({k: v for k, v in phases.items()
                        if k not in ordered})
        out = {"total_s": self.total_s,
               "accounted_s": sum(phases.values()),
               "phases_s": ordered}
        sub_s = self.sub_s
        if sub_s:
            sub = {k: sub_s[k] for k in SUB_PHASES if k in sub_s}
            sub.update({k: v for k, v in sub_s.items() if k not in sub})
            out["sub_phases_s"] = sub
        return out

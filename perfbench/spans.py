"""The program's own spans and counters in a run's window.

The program records spans and counter events in a bounded ring in its
own process (``repro.sim.observe.get_profiler().spans()``): name, start,
end and the enclosing span.  Every job of a cell is one root span, the
top-level call the cell's kind makes (``grid_sweep`` or
``closed_loop_score``).  The window's jobs are the ring's last
``len(ctx.jobs) + len(ctx.traced)`` roots of that name less the last
``len(ctx.traced)``: the traced jobs run after the window.  Other spans
join a job through their enclosing spans; ``gc_full`` spans, which
interrupt whatever runs, join the job whose root span holds them.

A program without the ring, a ring with fewer roots than the window's
jobs, or one that dropped a span of the window gives ``None``: no
number is read from a partial window.
"""
from __future__ import annotations

from typing import Dict, List, Optional

GC_FULL = "gc_full"


def window(ctx, root: str) -> Optional[List[Dict[str, list]]]:
    """Per job of the window, its spans and counter events by name."""
    from repro.sim.observe import get_profiler
    prof = get_profiler()
    if not hasattr(prof, "spans"):
        return None
    ring = prof.spans()
    n, t = len(ctx.jobs), len(ctx.traced)
    roots = [s for s in ring if s.name == root and s.parent is None]
    if n == 0 or len(roots) < n + t:
        return None
    roots = roots[len(roots) - n - t:len(roots) - t]
    # the ring keeps spans in the order they closed: every span it
    # dropped closed before its oldest one did
    if prof.dropped and ring[0].end_ns >= roots[0].start_ns:
        return None
    job_of = {r.seq: i for i, r in enumerate(roots)}
    parent_of = {s.seq: s.parent for s in ring}
    jobs: List[Dict[str, list]] = [{} for _ in roots]
    for s in ring:
        if s.name == GC_FULL:
            j = next((i for i, r in enumerate(roots)
                      if r.start_ns <= s.start_ns and s.end_ns <= r.end_ns),
                     None)
        else:
            p = s.parent
            while p is not None and p not in job_of:
                p = parent_of.get(p)
            j = job_of.get(p)
        if j is not None:
            jobs[j].setdefault(s.name, []).append(s)
    return jobs


def seconds(jobs: List[Dict[str, list]], name: str) -> Optional[float]:
    """Summed duration of the window's spans named ``name``; ``None``
    where the window holds none."""
    found = [s for job in jobs for s in job.get(name, ())]
    if not found:
        return None
    return sum((s.end_ns - s.start_ns) * 1e-9 for s in found)


def counted(jobs: List[Dict[str, list]], name: str) -> int:
    """Sum of the window's counter events named ``name``."""
    return sum(s.count for job in jobs for s in job.get(name, ())
               if s.count is not None)

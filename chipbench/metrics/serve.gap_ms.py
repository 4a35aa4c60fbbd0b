"""Median idle gap (ms) between consecutive device operations inside the
traced requests, of the gaps of 10 us or more: the host's hand-offs, such
as its round trip between decode steps.  Shorter gaps are two operations
of one program back to back (a few ns on a TPU v5e)."""
import statistics

MIN_NS = 10_000


def read(run):
    if run.trace is None:
        return None
    gaps = [g for s, e in run.trace.spans("request")
            for g in run.trace.gaps(s, e) if g >= MIN_NS]
    return statistics.median(gaps) * 1e-6 if gaps else None

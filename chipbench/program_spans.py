"""The program's own span records (``repro.runtime.spans``) inside the
measured window, for the metrics that split the benchmark's spans into
the program's layers.

"Per window", "per request" and "per map" divide by the number of the
benchmark's own spans around the calls (``admit``, ``dispatch``) in the
window, so a child metric adds up toward its parent's.  A program
without the recorder, or one whose recorder dropped records that may lie
inside the window, reads nothing.
"""
from __future__ import annotations

from chipbench.readings import window_spans


def records(run):
    """The program's records that lie inside the ``window`` span, or
    ``None``."""
    try:
        from repro.runtime.spans import RECORDER
    except ImportError:
        return None
    windows = [(t0, t1) for n, t0, t1 in run.spans if n == "window"]
    return RECORDER.inside(*windows[-1]) if windows else None


def named(run, name: str):
    """The window's records of the program span ``name``, or ``None``
    when there are none."""
    recs = records(run)
    if recs is None:
        return None
    return [r for r in recs if r.name == name] or None


def ms_per(run, name: str, per: str):
    """Summed time (ms) of the program's ``name`` spans in the window,
    over the number of the benchmark's ``per`` spans there."""
    recs = named(run, name)
    calls = len(window_spans(run, per))
    if recs is None or calls == 0:
        return None
    return sum(r.t1 - r.t0 for r in recs) * 1e3 / calls


def attr_sum(recs, key: str) -> float:
    return sum(r.attrs[key] for r in recs)

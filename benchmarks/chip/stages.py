"""What the readers of the program's stage histograms share.

A stage histogram (``tacz_*_stage_seconds{stage}``, ``tacz_gc_pause_
seconds``) is read over the window.  A program that lacks the family
reports nothing, so the metric stays out of its line; a program that has
it reports 0.0 for a stage that did not run in the window.
"""
from __future__ import annotations

REGIONS = {"route": "/v1/regions"}


def has_family(name: str) -> bool:
    """Whether the program's registry declares the family ``name``."""
    from repro.obs.metrics import REGISTRY

    return any(f.name == name for f in REGISTRY.families())


def ms_per_request(win, family: str, **labels):
    """Milliseconds per region request of the window in the family's
    series matching ``labels``; None when the window served no region
    request or the program lacks the family."""
    _, n = win.hist("tacz_http_request_seconds", **REGIONS)
    if not n or not has_family(family):
        return None
    seconds, _ = win.hist(family, **labels)
    return 1e3 * seconds / n


def s_per_snapshot(win, stage: str):
    """Seconds per published snapshot in one stage of
    ``tacz_compress_stage_seconds``; None when the window published no
    snapshot or the program never recorded the stage."""
    seconds, calls = win.hist("tacz_compress_stage_seconds", stage=stage)
    snaps = win.facts.get("snapshots", 0)
    return seconds / snaps if calls and snaps else None

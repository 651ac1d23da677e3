"""Seconds per snapshot in the compressor's ``entropy`` stage
(``tacz_compress_stage_seconds{stage="entropy"}`` over the window)."""


def read(win):
    seconds, calls = win.hist("tacz_compress_stage_seconds", stage="entropy")
    snaps = win.facts.get("snapshots", 0)
    return seconds / snaps if calls and snaps else None

"""Seconds per snapshot in the compressor's ``prequant`` stage
(``tacz_compress_stage_seconds{stage="prequant"}`` over the window)."""


def read(win):
    seconds, calls = win.hist("tacz_compress_stage_seconds", stage="prequant")
    snaps = win.facts.get("snapshots", 0)
    return seconds / snaps if calls and snaps else None

"""Seconds per snapshot in the TACZ writer, all stages
(``tacz_writer_level_seconds`` over the window)."""


def read(win):
    seconds, calls = win.hist("tacz_writer_level_seconds")
    snaps = win.facts.get("snapshots", 0)
    return seconds / snaps if calls and snaps else None

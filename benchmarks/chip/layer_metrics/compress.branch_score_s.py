"""Seconds per snapshot in the compressor's ``branch_score`` stage
(``tacz_compress_stage_seconds{stage="branch_score"}`` over the window)."""


def read(win):
    seconds, calls = win.hist("tacz_compress_stage_seconds",
                              stage="branch_score")
    snaps = win.facts.get("snapshots", 0)
    return seconds / snaps if calls and snaps else None

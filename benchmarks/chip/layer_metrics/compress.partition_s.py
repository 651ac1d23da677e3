"""Seconds per snapshot in the compressor's ``partition`` stage
(``tacz_compress_stage_seconds{stage="partition"}`` over the window):
the strategy choice and OpST/AKDTree placement of each level
(``partition_level``)."""
import stages


def read(win):
    return stages.s_per_snapshot(win, "partition")

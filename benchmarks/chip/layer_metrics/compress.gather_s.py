"""Seconds per snapshot in the compressor's ``gather`` stage
(``tacz_compress_stage_seconds{stage="gather"}`` over the window): the
extraction of the bricks from the level grid and their stacking into
shape groups for the batched predictor."""
import stages


def read(win):
    return stages.s_per_snapshot(win, "gather")

"""Seconds per snapshot in the compressor's ``recon`` stage
(``tacz_compress_stage_seconds{stage="recon"}`` over the window): the
reconstruction of the winning branch, per-brick results, and scatter of
the bricks into the masked level grid."""
import stages


def read(win):
    return stages.s_per_snapshot(win, "recon")

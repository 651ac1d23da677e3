"""Microseconds per sub-block in the three compress stages that walk a
level's sub-blocks one by one on the host: ``partition`` + ``gather`` +
``recon`` of ``tacz_compress_stage_seconds`` over the window, per
sub-block ``tacz_compress_subblocks_total`` counted in it.  Nothing is
reported by a program that lacks the sub-block counter or when the
window placed no sub-block."""
import stages

WALKS = ("partition", "gather", "recon")


def read(win):
    if not stages.has_family("tacz_compress_subblocks_total"):
        return None
    subblocks = win.counter("tacz_compress_subblocks_total")
    if not subblocks:
        return None
    seconds = sum(win.hist("tacz_compress_stage_seconds", stage=s)[0]
                  for s in WALKS)
    return 1e6 * seconds / subblocks

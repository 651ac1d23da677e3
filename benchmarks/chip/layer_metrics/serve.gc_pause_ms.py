"""Milliseconds per region request in the server process's garbage
collections, all generations (``tacz_gc_pause_seconds`` over the
window)."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_gc_pause_seconds")

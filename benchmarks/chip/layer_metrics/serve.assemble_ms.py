"""Milliseconds per region request in crop assembly
(``tacz_server_stage_seconds{stage="assemble"}`` over the window): every
box's crop of every level pasted from its bricks."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_server_stage_seconds",
                                 stage="assemble")

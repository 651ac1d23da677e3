"""Milliseconds per region request in reconstruction of decoded bricks
(``tacz_server_stage_seconds{stage="recon"}`` over the window): the
batched dequant and prediction replay, brick copies and cache inserts
inside the planner's decode."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_server_stage_seconds",
                                 stage="recon")

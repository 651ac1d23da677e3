"""Milliseconds per region request in the ``device`` stage of Huffman
payload decode (``tacz_entropy_decode_stage_seconds{stage="device"}``
over the window): from the first device call of a launch until its
results are on the host."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_entropy_decode_stage_seconds",
                                 stage="device")

"""Milliseconds per region request in the ``unpack`` stage of Huffman
payload decode (``tacz_entropy_decode_stage_seconds{stage="unpack"}`` over
the window): codebook symbol map and per-payload rows on the host."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_entropy_decode_stage_seconds",
                                 stage="unpack")

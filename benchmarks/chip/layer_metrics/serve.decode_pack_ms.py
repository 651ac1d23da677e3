"""Milliseconds per region request in the ``pack`` stage of Huffman payload
decode (``tacz_entropy_decode_stage_seconds{stage="pack"}`` over the
window): length sort, launch chunking, device tables and the bit-matrix
build on the host."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_entropy_decode_stage_seconds",
                                 stage="pack")

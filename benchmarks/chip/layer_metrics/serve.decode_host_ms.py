"""Milliseconds per region request in the ``host`` stage of Huffman
payload decode (``tacz_entropy_decode_stage_seconds{stage="host"}`` over
the window): payloads a guard (or a host engine) decodes on the host."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_entropy_decode_stage_seconds",
                                 stage="host")

"""Milliseconds per region request in Huffman payload decode
(``tacz_entropy_decode_seconds`` over the window); nothing when the
window decoded no payload."""


def read(win):
    seconds, calls = win.hist("tacz_entropy_decode_seconds")
    _, n = win.hist("tacz_http_request_seconds", route="/v1/regions")
    return 1e3 * seconds / n if n and calls else None

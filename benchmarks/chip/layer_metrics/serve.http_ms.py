"""Milliseconds per region request outside the decode units: HTTP
handling, admission and framing.  The server splits a request into one
decode unit per level and runs them side by side, so the request's HTTP
time (``tacz_http_request_seconds{route="/v1/regions"}``) is taken less
the mean unit time (``tacz_server_request_seconds`` over its count), not
less their sum: an upper estimate of this layer's own time."""


def read(win):
    http_s, n = win.hist("tacz_http_request_seconds", route="/v1/regions")
    unit_s, units = win.hist("tacz_server_request_seconds")
    if not n or not units:
        return None
    return 1e3 * (http_s / n - unit_s / units)

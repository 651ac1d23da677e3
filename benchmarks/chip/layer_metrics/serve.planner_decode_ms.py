"""Milliseconds per region request in the planner's decode of missing
sub-blocks, entropy decode and reconstruction
(``tacz_planner_decode_seconds`` over the window)."""


def read(win):
    seconds, _ = win.hist("tacz_planner_decode_seconds")
    _, n = win.hist("tacz_http_request_seconds", route="/v1/regions")
    return 1e3 * seconds / n if n else None

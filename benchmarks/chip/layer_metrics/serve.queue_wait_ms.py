"""Mean milliseconds a decode unit waited from admission until a decode
worker started it (``tacz_server_stage_seconds{stage="queue_wait"}``
over the window, per observation: one per unit); 0.0 when no unit ran."""
import stages


def read(win):
    _, n = win.hist("tacz_http_request_seconds", **stages.REGIONS)
    if not n or not stages.has_family("tacz_server_stage_seconds"):
        return None
    seconds, units = win.hist("tacz_server_stage_seconds",
                              stage="queue_wait")
    return 1e3 * seconds / units if units else 0.0

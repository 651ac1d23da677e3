"""Share of the roofline reached by the device Huffman decode
(``huffdec_windows`` + ``decode_walk``): the least time the chip needs to
read the decoded payloads' bits and write their codes as int32, over
those programs' device time in the trace.  Codes are the planner's decoded
float32 bytes over 4; payloads that a guard sent to the host are taken
out in proportion.  Counters are read over the traced part of the
window, as the device time is."""
import work


def read(win):
    if win.trace is None:
        return None
    tw = win.traced
    dev = tw.counter("tacz_device_items_total", stage="huffman_decode")
    host = tw.counter("tacz_host_fallbacks_total", stage="huffman_decode")
    seconds = win.trace.kernel_s("huffdec_windows", "decode_walk")
    if not dev or seconds <= 0:
        return None
    codes = tw.counter("tacz_planner_decoded_bytes_total") / 4.0
    nbytes = work.huffman_decode_bytes(codes, win.facts["bits_per_code"])
    return work.roofline_share(nbytes * dev / (dev + host), 0.0, seconds,
                               win.peaks)

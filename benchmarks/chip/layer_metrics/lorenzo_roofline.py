"""Share of the roofline reached by the device Lorenzo program
(``lorenzo3d_codes_batched``): the least time the chip needs to read each
brick cell as float32 and write its code as int32, over the program's
device time in the trace.  Nothing is reported when some bricks went to
the host, since the device time then covers only part of the cells, or
when the trace covers only part of the window, since the cells are
counted over the whole window."""
import work


def read(win):
    if win.trace is None or not win.whole_window_traced:
        return None
    on_device = win.counter("tacz_device_items_total", stage="lorenzo")
    if not on_device or on_device != win.facts.get("lorenzo_bricks"):
        return None
    seconds = win.trace.kernel_s("lorenzo3d_codes_batched")
    return work.roofline_share(work.lorenzo_bytes(win.facts["lorenzo_cells"]),
                               0.0, seconds, win.peaks)

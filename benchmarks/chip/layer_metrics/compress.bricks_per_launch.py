"""Bricks per device launch of the batched Lorenzo program:
``tacz_device_items_total{stage="lorenzo"}`` over
``tacz_device_launches_total{stage="lorenzo"}`` in the window.  Nothing
is reported by a program that lacks the launch counter or when no
Lorenzo launch ran (a CPU run computes the codes on the host)."""
import stages


def read(win):
    if not stages.has_family("tacz_device_launches_total"):
        return None
    launches = win.counter("tacz_device_launches_total", stage="lorenzo")
    if not launches:
        return None
    return win.counter("tacz_device_items_total", stage="lorenzo") / launches

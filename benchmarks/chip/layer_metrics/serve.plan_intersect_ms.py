"""Milliseconds per region request in the planner's sub-block lookups,
one per SHE level box (``tacz_planner_intersect_seconds`` over the
window): the part of the plan stage that searches the index."""
import stages


def read(win):
    return stages.ms_per_request(win, "tacz_planner_intersect_seconds")

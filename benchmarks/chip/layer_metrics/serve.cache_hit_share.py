"""Share of the sub-blocks the planner resolved from its cache
(``tacz_planner_subblocks_total``, cached over cached + decoded), in
percent."""


def read(win):
    cached = win.counter("tacz_planner_subblocks_total", outcome="cached")
    decoded = win.counter("tacz_planner_subblocks_total", outcome="decoded")
    total = cached + decoded
    return 100.0 * cached / total if total else None

"""Host ms per BFS level: the window over the levels its searches ran."""


def read(run):
    levels = run.counts.get("levels")
    return 1e3 * run.window_s / levels if levels else None
